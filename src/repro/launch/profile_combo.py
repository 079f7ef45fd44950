"""§Perf profiler: lower+compile one combo and print trip-weighted top ops by
HBM traffic / FLOPs / collective bytes — the evidence for each hypothesis.

  PYTHONPATH=src python -m repro.launch.profile_combo --arch rwkv6-7b \
      --shape train_4k --plan dp_tp --metric hbm_bytes
"""
import argparse

from repro.configs.base import SHAPES, get_config
from repro.core import hlo_analysis as ha
from repro.launch.dryrun import lower_combo
from repro.launch.mesh import make_production_mesh, use_cpu_devices


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--plan", default="dp_tp")
    ap.add_argument("--metric", default="hbm_bytes",
                    choices=("hbm_bytes", "flops"))
    ap.add_argument("--n", type=int, default=20)
    ap.add_argument("--collectives", action="store_true")
    args = ap.parse_args()
    use_cpu_devices(512)

    cfg = get_config(args.arch)
    mesh = make_production_mesh()
    lowered, _ = lower_combo(cfg, SHAPES[args.shape], mesh, args.plan)
    text = lowered.compile().as_text()
    rows = ha.top_ops(text, n=args.n, metric=args.metric)
    total = ha.analyze_hlo_text(text)
    print(f"total flops={total['flops']:.3e} hbm={total['hbm_bytes']:.3e} "
          f"coll={total['total_collective_bytes']:.3e}")
    print(f"--- top {args.n} by {args.metric} (trip-weighted, per device) ---")
    for cost, op, name, shape, hint in rows:
        print(f"{cost:12.4e}  {op:18s} {shape:28s} {hint}")
    if args.collectives:
        print("--- collectives ---")
        for k, v in total["collective_bytes"].items():
            print(f"{k:20s} {v:12.4e} bytes  x{total['collective_counts'][k]:.0f}")


if __name__ == "__main__":
    main()
