"""Backlog: batch inference. The queue holds ``backlog`` requests (waiting
or running) at every step, topped up as requests finish, so it never
empties and the decode batch stays full.

Lengths are the quantiles of the mix's distributions, 256 at a time, in
the orders the traffic file's ``order_seed`` draws; the run's seed draws
the token ids. A warm-in of ``warm_in_s`` seconds fills the batch before
the window opens. With ``--trace 1`` a stretch of the same backlog after
the close is traced.

End to end: ``output_tok_s``, the generated tokens whose values reached the
host inside the window, over the window's length.
"""
from __future__ import annotations

import time

from perfbench import serving

MIX = 256


def run(ctx):
    traffic = ctx.cell["traffic"]
    eng, s, timings = serving.build(ctx.cell, ctx.seed)
    draws = serving.Draws(ctx.seed, traffic)
    t = time.perf_counter()
    serving.warm_paths(eng, s["vocab"], draws.tokens)
    timings["paths_s"] = time.perf_counter() - t
    client = serving.Client(eng)
    pool, added = [], []

    def refill(now):
        out = []
        while client.in_flight() + len(out) < traffic["backlog"]:
            if not pool:
                pool.extend(serving.make_items(draws, traffic, MIX,
                                               s["vocab"], False))
            out.append(pool.pop())
        added.extend(out)
        return out

    t = client.clock()
    client.pump(t + traffic["warm_in_s"], refill=refill)
    timings["warm_in_s"] = client.clock() - t
    t_open = ctx.open_window()
    t_close = t_open + ctx.seconds
    client.pump(t_close, refill=refill)
    ctx.close_window()
    served = sum(1 for it in added for w in it.times
                 if t_open <= w <= t_close)
    touched = [it for it in added
               if any(t_open <= w <= t_close for w in it.times)]
    for it in added:                  # the window finished these
        it.counted = it.done and t_open <= it.times[-1] <= t_close
    ctx.log(f"backlog: {served} tokens on the host in the window, "
            f"{len(touched)} requests served, "
            f"{sum(it.counted for it in added)} finished; "
            f"{client.steps} engine steps")
    # the profiler's start and stop hold the host: trace the same backlog
    # after the close
    cap, work = serving.traced_stretch(ctx, client, refill=refill)
    e2e = {"output_tok_s": served / ctx.seconds}
    out = serving.finish(ctx, client, s, timings, added, cap, work, e2e)
    out.update(attempted=len(touched), failed=0)
    return out
