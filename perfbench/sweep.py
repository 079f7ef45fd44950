"""Find an open-loop cell's knee: the highest rate at which completions keep
pace with arrivals through the window. One process, one set of weights.

  python perfbench/sweep.py phi4mini.chat --seed 3 --seconds 40 \
      --rates 0.4 0.8 1.2

For each rate: a warm-in, a window of ``--seconds`` at that rate, then a
drain. Prints one JSON line per rate: requests due in the window, those
finished by the close, those still waiting at the close, and the TTFT and
inter-token tails. The rate for the cell (about 0.8 x the knee) is then
written into its traffic file by hand.
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
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    from perfbench import bench, serving
    from perfbench.drivers import open_loop
    cell = bench.load_cell(args.workload)
    (require or bench.require_chips)(cell["chips"])
    bench.enable_compile_cache()
    eng, s, _ = serving.build(cell, args.seed)
    draws = serving.Draws(args.seed, cell["traffic"])
    serving.warm_paths(eng, s["vocab"], draws.tokens)
    for rate in args.rates:
        traffic = dict(cell["traffic"], rate_rps=rate)
        client = serving.Client(eng)
        t = client.clock()
        warm = traffic["warm_in_s"]
        client.queue.extend(open_loop.schedule(draws, traffic, t, warm,
                                               s["vocab"], False))
        client.pump(t + warm)
        t_open = client.clock()
        t_close = t_open + args.seconds
        window = open_loop.schedule(draws, traffic, t_open, args.seconds,
                                    s["vocab"], True)
        client.queue.extend(window)
        client.pump(t_close)
        finished = sum(1 for it in client.items.values()
                       if it.done and it.times[-1] <= t_close)
        waiting = len(eng.scheduler.waiting)
        client.pump(t_close + serving.DRAIN_CAP_S,
                    done=lambda: not client.queue
                    and not eng.scheduler.has_work)
        lat = serving.latency_metrics(window, t_close)
        tt = lat["ttft_ms"]
        print(json.dumps({
            "rate_rps": rate, "due": lat["attempted"],
            "finished_by_close": finished, "waiting_at_close": waiting,
            "unfinished": lat["failed"],
            "ttft_p50_ms": bench.percentile(tt, 50),
            "ttft_p75_ms": bench.percentile(tt, 75),
            "ttft_p90_ms": bench.percentile(tt, 90),
            "ttft_max_ms": max(tt),
            "itl_p50_ms": bench.percentile(lat["itl_ms"], 50),
            "itl_p95_ms": bench.percentile(lat["itl_ms"], 95),
            "steps": client.steps}), flush=True)
        eng.drain()


if __name__ == "__main__":
    main()
