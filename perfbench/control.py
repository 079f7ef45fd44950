"""Readings that the limit of a cell's comparison is set from, one process,
several seeds.

  python perfbench/control.py phi4mini.chat --seconds 20 --seeds 1 2 3

For each seed the cell's own driver runs (a short window at the cell's own
load), then the plain float32 reference reads the widest gap of the served
tokens, and the same reference computed in fp8 (the control) reads the
widest gap of the tokens it would put first. Prints one JSON line per seed.
The program's readings set the lower end of the limit, the control's the
upper end.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None, require=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--no-control", action="store_true",
                    help="read the program only")
    args = ap.parse_args(argv)
    from perfbench import bench
    from perfbench.run import Run
    cell = bench.load_cell(args.workload)
    devs = (require or bench.require_chips)(cell["chips"])
    bench.enable_compile_cache()
    compiles = bench.CompileLog()
    driver = bench.load_module("drivers", cell["traffic"]["driver"])
    for seed in args.seeds:
        ns = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0)
        ctx = Run(cell, ns, devs, compiles, bench.log)
        ctx.control = not args.no_control
        res = driver.run(ctx)
        print(json.dumps({"seed": seed, **res["reference"],
                          "attempted": res["attempted"],
                          "failed": res["failed"]}), flush=True)


if __name__ == "__main__":
    main()
