"""Multi-pod dry-run (deliverable (e)) + roofline extraction (deliverable (g)).

For a given (architecture × input shape × mesh × plan):
  1. build the production mesh (16×16 single-pod / 2×16×16 multi-pod),
  2. lower + compile train_step (train/prefill shapes) or serve_step
     (decode shapes) against ShapeDtypeStruct inputs — no allocation,
  3. print memory_analysis() (fits?) and cost_analysis() (FLOPs/bytes),
  4. parse collective bytes from the optimized HLO,
  5. emit roofline terms + MODEL_FLOPS ratio as JSON.

Run one combination per process (the 512 virtual CPU devices are locked in
at jax init):  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b \
            --shape train_4k --mesh single --plan dp_tp
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, get_config
from repro.core import costmodel as cm
from repro.core import hlo_analysis as ha
from repro.core import parallelism as par
from repro.launch.mesh import make_production_mesh, use_cpu_devices
from repro.launch.specs import input_specs, shape_applicable
from repro.models import transformer as T
from repro.optim import make_optimizer
from repro.train import trainer
from repro.serving import serve


def lower_combo(cfg, shape, mesh, plan_name, cfg_overrides=None, accum_steps=1):
    import dataclasses
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    plan = par.make_plan(plan_name, mesh)
    specs = input_specs(cfg, shape)
    optimizer = make_optimizer("adam", lr=1e-4)

    if shape.kind in ("train", "prefill"):
        state_abs = trainer.abstract_state(cfg, optimizer)
        if shape.kind == "train":
            step = trainer.make_train_step(cfg, optimizer, plan,
                                           accum_steps=accum_steps)
            st_sh = trainer.state_shardings(state_abs, plan)
            b_sh = plan.batch_shardings(specs["batch"])
            rep = NamedSharding(plan.mesh, P())
            jitted = jax.jit(step, in_shardings=(st_sh, b_sh),
                             out_shardings=(st_sh, {"loss": rep}),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_abs, specs["batch"])
        else:
            # prefill: forward pass producing last-position logits
            def prefill(params, batch):
                with par.plan_context(plan):
                    hidden, _ = T.forward(cfg, params, batch)
                return T.logits(cfg, params, hidden[:, -1:, :])

            p_sh = plan.param_shardings(state_abs["params"])
            b_sh = plan.batch_shardings(specs["batch"])
            jitted = jax.jit(prefill, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(state_abs["params"], specs["batch"])
        tokens = shape.global_batch * shape.seq_len
    else:
        params_abs = jax.eval_shape(
            lambda k: T.init_params(cfg, k), jax.random.PRNGKey(0))
        step = serve.make_serve_step(cfg, plan)
        p_sh = plan.param_shardings(params_abs)
        c_sh = plan.cache_shardings(specs["cache"])
        i_sh = jax.tree.map(
            lambda l: NamedSharding(mesh, plan.spec_for_batch_leaf("token", l.shape)),
            specs["inputs"])
        rep = NamedSharding(mesh, P())
        jitted = jax.jit(step, in_shardings=(p_sh, c_sh, i_sh, rep),
                         out_shardings=(None, c_sh), donate_argnums=(1,))
        lowered = jitted.lower(params_abs, specs["cache"], specs["inputs"],
                               jax.ShapeDtypeStruct((), jnp.int32))
        tokens = shape.global_batch  # ONE new token per sequence

    return lowered, tokens


def run(arch, shape_name, mesh_kind, plan_name, out_path=None, quiet=False,
        accum_steps=1):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "plan": plan_name, "accum_steps": accum_steps,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        _emit(rec, out_path, quiet)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    lowered, tokens = lower_combo(cfg, shape, mesh, plan_name,
                                  accum_steps=accum_steps)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    analysis = ha.analyze_compiled(lowered, compiled)
    mem = analysis["memory"]
    if not quiet:
        print("memory_analysis:", json.dumps(mem, indent=1))
        print("cost_analysis (xla, loop-unaware): flops=%.3e bytes=%.3e"
              % (analysis["xla_cost_flops"], analysis["xla_cost_bytes"]))
        print("loop-aware: flops=%.3e hbm=%.3e coll=%.3e"
              % (analysis["flops"], analysis["hbm_bytes"],
                 analysis["collectives"]["total"]))

    # parsed quantities are per-device (SPMD module); normalize to global
    flops_dev = analysis["flops"]
    bytes_dev = analysis["hbm_bytes"]
    coll_dev = analysis["collectives"]["total"]
    mf = cm.model_flops(cfg.active_param_count(), tokens)
    if shape.kind == "train":
        mf *= 1.0  # 6ND already includes fwd+bwd
    else:
        mf /= 3.0  # forward only: 2ND

    global_flops = flops_dev * chips
    terms = cm.roofline_terms(global_flops, bytes_dev * chips, coll_dev * chips, chips)
    hbm_need = (mem.get("argument_size_in_bytes", 0) + mem.get("temp_size_in_bytes", 0)
                + mem.get("output_size_in_bytes", 0) - mem.get("alias_size_in_bytes", 0))

    rec.update({
        "status": "ok",
        "chips": chips,
        "tokens": tokens,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_dev,
        "collectives": analysis["collectives"],
        "memory": mem,
        "hbm_needed_per_device": hbm_need,
        "fits_hbm": bool(hbm_need < cm.V5E.hbm_bytes),
        "model_flops": mf,
        "useful_flops_ratio": (mf / global_flops) if global_flops else None,
        "roofline": terms,
        "dominant": cm.dominant_term(terms),
    })
    _emit(rec, out_path, quiet)
    return rec


def _emit(rec, out_path, quiet):
    if out_path:
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
    if not quiet:
        slim = {k: v for k, v in rec.items() if k not in ("collectives", "memory")}
        print(json.dumps(slim, indent=1, default=str))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=("single", "multi"))
    ap.add_argument("--plan", default="dp_tp")
    ap.add_argument("--out", default=None)
    ap.add_argument("--accum", type=int, default=1)
    args = ap.parse_args()
    use_cpu_devices(512)
    try:
        rec = run(args.arch, args.shape, args.mesh, args.plan, args.out,
                  accum_steps=args.accum)
        sys.exit(0 if rec.get("status") in ("ok", "skipped") else 1)
    except Exception:
        traceback.print_exc()
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"arch": args.arch, "shape": args.shape,
                           "mesh": args.mesh, "plan": args.plan,
                           "status": "error",
                           "error": traceback.format_exc()[-2000:]}, f, indent=1)
        sys.exit(1)


if __name__ == "__main__":
    main()
