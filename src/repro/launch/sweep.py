"""Drive the full dry-run matrix: every (arch × shape × mesh), one process
per combination (jax locks the 512 fake devices at init). Results land in
results/dryrun/<arch>__<shape>__<mesh>__<plan>.json; existing files are
skipped, so the sweep is resumable.

  PYTHONPATH=src python -m repro.launch.sweep --mesh single multi --plan dp_tp
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from repro.configs.base import ARCH_MODULES, SHAPES, get_config

ARCHS = [
    "gemma3-12b", "phi4-mini-3.8b", "qwen2-vl-2b", "mixtral-8x7b",
    "stablelm-3b", "rwkv6-7b", "yi-9b", "qwen3-moe-30b-a3b",
    "zamba2-2.7b", "musicgen-medium",
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", nargs="+", default=["single", "multi"])
    ap.add_argument("--plan", nargs="+", default=["dp_tp"])
    ap.add_argument("--arch", nargs="+", default=ARCHS)
    ap.add_argument("--shape", nargs="+", default=list(SHAPES))
    ap.add_argument("--outdir", default="results/dryrun")
    ap.add_argument("--timeout", type=int, default=1800)
    args = ap.parse_args()
    os.makedirs(args.outdir, exist_ok=True)

    combos = [(a, s, m, p) for a in args.arch for s in args.shape
              for m in args.mesh for p in args.plan]
    t_start = time.time()
    n_ok = n_skip = n_err = 0
    for i, (arch, shape, mesh, plan) in enumerate(combos):
        out = os.path.join(args.outdir, f"{arch}__{shape}__{mesh}__{plan}.json")
        if os.path.exists(out):
            with open(out) as f:
                st = json.load(f).get("status")
            if st in ("ok", "skipped"):
                continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape, "--mesh", mesh,
               "--plan", plan, "--out", out]
        t0 = time.time()
        try:
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=args.timeout,
                               env={**os.environ, "PYTHONPATH": "src"})
            status = "?"
            if os.path.exists(out):
                with open(out) as f:
                    status = json.load(f).get("status")
            if status == "ok":
                n_ok += 1
            elif status == "skipped":
                n_skip += 1
            else:
                n_err += 1
                tail = (r.stderr or r.stdout or "")[-800:]
                print(f"[{i+1}/{len(combos)}] {arch} {shape} {mesh} ERROR\n{tail}",
                      flush=True)
                continue
            print(f"[{i+1}/{len(combos)}] {arch} {shape} {mesh} {plan}: "
                  f"{status} ({time.time()-t0:.0f}s)", flush=True)
        except subprocess.TimeoutExpired:
            n_err += 1
            with open(out, "w") as f:
                json.dump({"arch": arch, "shape": shape, "mesh": mesh,
                           "plan": plan, "status": "error",
                           "error": "timeout"}, f)
            print(f"[{i+1}/{len(combos)}] {arch} {shape} {mesh}: TIMEOUT", flush=True)
    print(f"done in {time.time()-t_start:.0f}s: ok={n_ok} skip={n_skip} err={n_err}")
    if n_err:
        sys.exit(1)


if __name__ == "__main__":
    main()
