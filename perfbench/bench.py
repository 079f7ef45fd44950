"""What every cell shares: the manifest, the chip check, the compile cache,
compile counting, and the result line.

A cell is found by its name in ``BENCHMARK.json``. Its configuration file
is ``perfbench/configs/<config>.json``, which names its architecture
(``perfbench/arch/<arch>.py``), and its traffic file
``perfbench/traffic/<traffic>.json``; the traffic file names the driver
(``perfbench/drivers/<driver>.py``), and each per-layer metric is read by
``perfbench/metrics/<metric>.py`` or, for a metric named ``<base>.<part>``,
by ``perfbench/metrics/<base>.py``. Adding a cell, a configuration, a mix
or a metric adds files and manifest entries; nothing here changes.
"""
from __future__ import annotations

import collections
import importlib
import importlib.util
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class BenchError(RuntimeError):
    """The cell cannot run as described."""


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The workload entry with its configuration file, traffic file and the
    metrics it reports, end to end and per layer."""
    man = manifest()
    found = [w for w in man["workloads"] if w["name"] == name]
    if not found:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = dict(found[0])

    def reports(m):
        return "workloads" not in m or name in m["workloads"]

    cell["spec"] = _load_json("configs", f"{cell['config']}.json")
    where = f"perfbench/configs/{cell['config']}.json"
    arch = cell["spec"].get("arch")
    if not arch:
        raise BenchError(f"{where} names no 'arch'")
    if not os.path.exists(os.path.join(HERE, "arch", f"{arch}.py")):
        raise BenchError(f"{where} names arch {arch!r}: there is no "
                         f"perfbench/arch/{arch}.py")
    cell["traffic"] = _load_json("traffic", f"{cell['traffic']}.json")
    cell["end_to_end"] = [m for m in man["end_to_end"] if reports(m)]
    moved = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [m for m in man["per_layer"]
                         if reports(m) and m["moves"] in moved]
    cell["run_seconds"] = man["run_seconds"]
    return cell


def load_module(kind: str, name: str):
    """``perfbench/<kind>/<name>.py``; a dotted name falls back to the
    module of its first part."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(HERE, kind, f"{stem}.py")
        if not os.path.exists(path):
            continue
        if "." not in stem:
            return importlib.import_module(f"perfbench.{kind}.{stem}")
        spec = importlib.util.spec_from_file_location(
            f"perfbench_{kind}_{stem}".replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    raise BenchError(f"no perfbench/{kind}/{name}.py")


def require_chips(n: int):
    """The devices JAX sees, when they are at least ``n`` accelerators."""
    import jax
    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        raise NoChip(f"no accelerator: JAX sees {len(devs)} "
                     f"{devs[0].platform} device(s)")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def enable_compile_cache() -> str:
    """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says, else
    at ``<checkout>/.jax_cache``: a fixed path, so runs share it. Every
    program is kept, however quickly it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileLog:
    """Backend compiles (in all and by function) and persistent-cache hits,
    from JAX's monitoring events."""
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.hits = self.misses = 0
        self.names = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == self.BACKEND:
            self.seconds += duration
            self.compiles += 1
            self.names[kw.get("fun_name", "?")] += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.seconds,
                "cache_hits": self.hits, "cache_misses": self.misses}


def percentile(values, q: float) -> float:
    """The ``q``-th percentile, interpolated linearly between ranks."""
    xs = sorted(values)
    if not xs:
        raise BenchError("no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def device_info(devs, trace=None) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if trace is not None:
        out["busy_s"] = trace["busy_s"]
        out["window_s"] = trace["window_s"]
    return out


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
