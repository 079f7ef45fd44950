"""Open loop: requests due at the mix's rate in wall-clock time, whether or
not earlier ones are done.

The arrivals of a window of ``seconds`` are ``round(rate * seconds)``
requests whose gaps and lengths are the quantiles of the mix's
distributions: every seed offers the same work, in the order the traffic file's ``order_seed`` draws; the run's
seed draws the token ids (and the weights). A warm-in of ``warm_in_s``
seconds of the same traffic fills the batch before the window opens.
After the close, arrivals go on (uncounted) while the window's requests
finish, for up to ``serving.DRAIN_CAP_S``; one still unfinished then has
failed. With ``--trace 1`` a stretch of that same load after the close
is traced.

End to end: ``ttft_p75_ms`` over every request due in the window, from
when it was due to when its first token is on the host; ``itl_p95_ms``
over every gap between two consecutive tokens of those requests. A
window of 51 s at chat's rate holds about 41 requests: the 75th
percentile is the highest with ten of them beyond it.
"""
from __future__ import annotations

import time

import numpy as np

from perfbench import bench, serving


def schedule(draws, traffic, t0, seconds, vocab, counted):
    """Requests due in ``(t0, t0 + seconds]`` at the mix's rate: all of its
    gaps, the last request due at the end."""
    n = max(1, round(traffic["rate_rps"] * seconds))
    items = serving.make_items(draws, traffic, n, vocab, counted)
    gaps = draws.order.permutation(serving.quantile_gaps(
        n, traffic["rate_rps"]))
    gaps *= seconds / gaps.sum()
    due = t0 + np.cumsum(gaps)
    for it, d in zip(items, due):
        it.due = float(d)
    return items


def run(ctx):
    traffic = ctx.cell["traffic"]
    eng, s, timings = serving.build(ctx.cell, ctx.seed)
    draws = serving.Draws(ctx.seed, traffic)
    t = time.perf_counter()
    serving.warm_paths(eng, s["vocab"], draws.tokens)
    timings["paths_s"] = time.perf_counter() - t
    client = serving.Client(eng)
    t = client.clock()
    warm = traffic["warm_in_s"]
    client.queue.extend(schedule(draws, traffic, t, warm, s["vocab"], False))
    client.pump(t + warm)
    timings["warm_in_s"] = client.clock() - t
    window = schedule(draws, traffic, 0.0, ctx.seconds, s["vocab"], True)
    after = schedule(draws, traffic, ctx.seconds, serving.DRAIN_CAP_S,
                     s["vocab"], False)
    t_open = ctx.open_window()
    t_close = t_open + ctx.seconds
    for it in window + after:
        it.due += t_open
    client.queue.extend(window + after)
    client.pump(t_close)
    ctx.close_window()
    # the profiler's start and stop hold the host for seconds: trace the
    # same load just after the close, once every request of the window is
    # admitted, so that no number of the window pays for it
    tracer = eng.telemetry.tracer
    admitted = lambda: all(it.rid is not None and tracer.first(
        it.rid, "admit") is not None for it in window)
    client.pump(t_close + serving.DRAIN_CAP_S, done=admitted)
    cap, work = serving.traced_stretch(ctx, client)
    client.pump(t_close + serving.DRAIN_CAP_S,
                done=lambda: all(it.done for it in window))
    lat = serving.latency_metrics(window, t_close)
    late = np.asarray(client.lateness) * 1e3
    ctx.log(f"open loop: {lat['attempted']} requests due in the window, "
            f"{lat['failed']} unfinished; {len(lat['itl_ms'])} token gaps; "
            f"TTFT samples beyond p75: "
            f"{lat['attempted'] - int(np.ceil(0.75 * lat['attempted']))}; "
            f"generator late by p50 {np.median(late):.2f} ms, max "
            f"{late.max():.2f} ms; {client.steps} engine steps")
    e2e = {"ttft_p75_ms": bench.percentile(lat["ttft_ms"], 75),
           "itl_p95_ms": bench.percentile(lat["itl_ms"], 95)}
    out = serving.finish(ctx, client, s, timings, window, cap, work, e2e)
    out.update(attempted=lat["attempted"], failed=lat["failed"])
    return out
