"""90th percentile of the wait from a request's arrival to its admission,
over the window's requests, from the engine's lifecycle tracer."""
from perfbench import bench


def read(obs, name):
    waits = obs.get("queue_wait_ms")
    return bench.percentile(waits, 90) if waits else None
