"""Run one benchmark cell on the chips of this machine and print its result.

  python perfbench/run.py --workload phi4mini.chat --seed 7 --seconds 51 \
      --trace 0

The cell is looked up in ``BENCHMARK.json`` and driven by the driver its
traffic file names. With ``--trace 0`` the result line carries the cell's
end-to-end metrics; with ``--trace 1`` a profiled stretch of the window
gives its per-layer metrics, ``device.busy_s``/``window_s`` and a
``breakdown``. Either way the run ends by comparing what the timed path
produced with the plain reference, and prints each number compared beside
its limit, last on standard error and last in the result line.

Exits 2, with no result line, when JAX finds no accelerator or fewer chips
than the cell asks for. The last line of standard output is the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


class Run:
    """What a driver is given: the cell, the run's arguments, the chips,
    and the marks of the measured window."""

    def __init__(self, cell, args, devs, compiles, log):
        self.cell, self.devs, self.compiles, self.log = cell, devs, compiles, log
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace = bool(args.trace)
        self.keep_trace = os.environ.get("PERFBENCH_KEEP_TRACE")
        self.setup_s = None
        self.window_compiles = None

    def open_window(self) -> float:
        t = time.perf_counter()
        self.setup_s = t - T_START
        self._compiles = self.compiles.compiles
        self._names = dict(self.compiles.names)
        self.log(f"set-up {self.setup_s:.3f} s; compiles so far "
                 f"{self.compiles.snapshot()}")
        return t

    def close_window(self) -> None:
        self.window_compiles = self.compiles.compiles - self._compiles
        if self.window_compiles:
            self.log("compiled inside the window: " + ", ".join(
                f"{k} x{v - self._names.get(k, 0)}"
                for k, v in self.compiles.names.items()
                if v > self._names.get(k, 0)))


def main(argv=None, require=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from perfbench import bench
    log = bench.log
    cell = bench.load_cell(args.workload)
    try:
        devs = (require or bench.require_chips)(cell["chips"])
    except bench.NoChip as e:
        log(f"refused: {e}")
        return 2
    log(f"cell {cell['name']}: {cell['why']}")
    log(f"devices: {devs[0].device_kind} x {len(devs)}; compile cache "
        f"{bench.enable_compile_cache()}")
    ctx = Run(cell, args, devs, bench.CompileLog(), log)
    driver = bench.load_module("drivers", cell["traffic"]["driver"])
    res = driver.run(ctx)

    t = res["timings"]
    log("set-up split (s): " + ", ".join(
        f"{k} {v:.3f}" for k, v in t.items())
        + f"; other {ctx.setup_s - sum(t.values()):.3f}")
    log(f"compiles inside the window: {ctx.window_compiles}")
    if args.trace:
        metrics = {}
        for m in cell["per_layer"]:
            v = bench.load_module("metrics", m["name"]).read(res["obs"],
                                                             m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(res["e2e"], setup_s=ctx.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics,
            "device": res["device"]}
    if args.trace and res["obs"]["trace"] is not None:
        line["breakdown"] = res["obs"]["trace"]["breakdown"]
    line["checks"] = res["checks"]
    for name, c in res["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
