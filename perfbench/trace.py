"""Reduce a JAX profiler trace to device busy time, idle gaps and op times.

  python perfbench/trace.py <profile dir>     # describe a trace's planes

``capture`` runs a span of the caller's loop under ``jax.profiler``, inside
a host annotation ``bench/traced_window`` that marks the window on the
trace's own clock. ``reduce`` reads the ``.xplane.pb`` it wrote: device
planes are ``/device:<TPU|GPU>:<n>``; their op events (line ``XLA Ops``)
give busy time as the union of op intervals inside the window, and their
program events (line ``XLA Modules``) give each jitted program's device
time. Host annotations (``engine/...``, ``bench/...``) label each idle gap
by what the host was doing at its middle. All times are seconds.
"""
from __future__ import annotations

import collections
import glob
import os
import re
import shutil
import sys
import tempfile

WINDOW = "bench/traced_window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_LABEL = re.compile(r"^(engine|bench)/")


def union_length(intervals):
    """Total length covered by ``(start, end)`` intervals."""
    total, end = 0.0, None
    start = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            if end is not None:
                total += end - start
            start, end = a, b
        else:
            end = max(end, b)
    if end is not None:
        total += end - start
    return total


def gaps(intervals, lo, hi):
    """The stretches of ``[lo, hi]`` no interval covers."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def label_gaps(idle, spans, top=10):
    """The ``top`` longest idle gaps, each named by the innermost host span
    (shortest) that covers its middle, else ``none``."""
    out = []
    for a, b in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        cover = [(e - s, n) for s, e, n in spans if s <= mid <= e]
        out.append([min(cover)[1] if cover else "none", b - a])
    return out


def summarize(device_ops, modules, host_spans, window):
    """Busy time, idle share and breakdown inside ``window`` = (lo, hi).
    ``device_ops``/``modules``: per device, lists of (start, end, name);
    ``host_spans``: (start, end, name)."""
    lo, hi = window
    length = hi - lo

    def clip(evs):
        return [(max(a, lo), min(b, hi), n) for a, b, n in evs
                if b > lo and a < hi]

    busy, op_time, mod_time, mod_count = [], collections.Counter(), \
        collections.Counter(), collections.Counter()
    idle_all = []
    for dev, evs in device_ops.items():
        evs = clip(evs)
        busy.append(union_length([(a, b) for a, b, _ in evs]))
        for a, b, n in evs:
            op_time[n] += b - a
        idle_all.extend(gaps([(a, b) for a, b, _ in evs], lo, hi))
    for dev, evs in modules.items():
        for a, b, n in clip(evs):
            mod_time[n] += b - a
            mod_count[n] += 1
    n_dev = max(len(device_ops), 1)
    busy_s = sum(busy) / n_dev
    return {
        "window_s": length,
        "busy_s": busy_s,
        "devices": len(device_ops),
        "op_s": {k: v / n_dev for k, v in op_time.items()},
        "module_s": {k: v / n_dev for k, v in mod_time.items()},
        "module_n": {k: v / n_dev for k, v in mod_count.items()},
        "breakdown": {
            "device_ops": [[n, s] for n, s in sorted(
                ((k, v / n_dev) for k, v in op_time.items()),
                key=lambda kv: -kv[1])[:10]],
            "idle_gaps": label_gaps(idle_all, clip(host_spans)),
        },
    }


def op_name(text):
    """An op event's name without its HLO text: ``%fusion.12 = f32[..]
    fusion(..)`` becomes ``%fusion.12``."""
    return text.split(" = ", 1)[0]


def _events(line, short=False):
    for ev in line.events:
        start = ev.start_ns * 1e-9
        name = op_name(ev.name) if short else ev.name
        yield start, start + ev.duration_ns * 1e-9, name


def read(path):
    """(device_ops, modules, host_spans, window) from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops, modules, host = {}, {}, []
    window = None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                device_ops[plane.name] = list(_events(lines["XLA Ops"],
                                                      short=True))
            if "XLA Modules" in lines:
                modules[plane.name] = list(_events(lines["XLA Modules"]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for a, b, n in _events(line):
                    if n == WINDOW:
                        window = (a, b)
                    elif HOST_LABEL.match(n):
                        host.append((a, b, n))
    if window is None:
        raise RuntimeError(f"no {WINDOW} annotation in {path}")
    if not device_ops:
        raise RuntimeError(f"no device op events in {path}")
    return device_ops, modules, host, window


def find_xplane(logdir):
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise RuntimeError(f"no .xplane.pb under {logdir}")
    return found[-1]


class capture:
    """``with capture() as cap: loop()`` traces the loop; ``cap.reduce()``,
    called once the measured window is over, returns ``summarize``'s result.
    The trace is written under ``TMPDIR`` and deleted once read, unless
    ``keep`` names a directory to copy it to."""

    def __init__(self, keep=None):
        self.keep = keep

    def __enter__(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="perfbench_trace_")
        jax.profiler.start_trace(self.dir)
        self._ann = jax.profiler.TraceAnnotation(WINDOW)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        self._ann.__exit__(*exc)
        jax.profiler.stop_trace()
        return False

    def reduce(self):
        try:
            if self.keep:
                shutil.copytree(self.dir, self.keep, dirs_exist_ok=True)
            return summarize(*read(find_xplane(self.dir)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def describe(logdir, top=15):
    """Each plane's lines with their event counts and most common names."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(logdir))
    for plane in pd.planes:
        print(plane.name)
        for line in plane.lines:
            names = collections.Counter(ev.name for ev in line.events)
            print(f"  {line.name!r}: {sum(names.values())} events; "
                  f"{names.most_common(top)}")


if __name__ == "__main__":
    describe(sys.argv[1])
