"""What the program's own instrumentation adds to a JAX profiler trace: host
self time per engine span, device idle time per host span, and device time
per model scope.

  python perfbench/spans.py <profile dir>    # print them, and a few ops

``trace.summarize`` reads a trace from outside: busy time, whole programs,
op names, the span over each of the ten longest idle gaps. The engine's
host loop nests its spans (``engine/step`` holds ``engine/schedule``, the
dispatches, ``engine/emit``), and the model's programs name their parts
with ``jax.named_scope`` (``attn``, ``kv_write``, ``mlp``, ``head``,
``optimizer``), which the compiler keeps in each instruction's
``op_name``. ``extend`` reduces both to the keys below, on the same window
and clock as ``summarize``:

  host_self_s   per span name, its time less what its child spans cover
  host_n        per span name, how many lie (in part) in the window
  idle_under    device idle time per innermost span over each gap's middle
  scope_s       device time of leaf ops per scope (``other`` for none);
                loops, calls and branches, which hold other ops, left out
  module_scope_s  the same per program (``XLA Modules`` event name)

A TPU trace's op events carry no ``op_name``. Each program's optimized HLO
rides in the trace's ``/host:metadata`` plane, under the program's event
name; an op is matched to the program whose event covers it on its device,
and to the instruction of its name there. ``jax.profiler.ProfileData``
does not expose that plane, so ``hlo_op_names`` reads the protobuf wire
format itself. All times are seconds.
"""
from __future__ import annotations

import bisect
import collections
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from perfbench import trace  # noqa: E402

SCOPES = ("attn", "kv_write", "mlp", "head", "optimizer")
# a path component naming a scope, maybe inside transforms: jvp(head)
SCOPE_PART = re.compile(r"^(?:[\w.\-]*\()*(%s)\)*$" % "|".join(SCOPES))
# ops whose time holds other ops' time: a scan's loop, a call, a branch
CONTAINER = re.compile(r"^%?(while|call|conditional)(\.\d+)?$")


# ------------------------------------------------------------- host spans
def nest(spans):
    """For ``spans`` = [(start, end, name)], nested as one thread's
    annotations are: the index of the innermost other span that contains
    each (None at the top), and the indices sorted by (start, -end), which
    puts a parent before its children."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][0], -spans[i][1]))
    parent, stack = [None] * len(spans), []
    for i in order:
        a, b = spans[i][0], spans[i][1]
        while stack and not (spans[stack[-1]][0] <= a
                             and b <= spans[stack[-1]][1]):
            stack.pop()
        parent[i] = stack[-1] if stack else None
        stack.append(i)
    return parent, order


def host_times(spans):
    """({name: self seconds}, {name: count}) over ``spans``."""
    parent, _ = nest(spans)
    kids = collections.defaultdict(list)
    for i, p in enumerate(parent):
        if p is not None:
            kids[p].append(spans[i][:2])
    self_s, count = collections.Counter(), collections.Counter()
    for i, (a, b, name) in enumerate(spans):
        count[name] += 1
        self_s[name] += (b - a) - trace.union_length(kids[i])
    return dict(self_s), dict(count)


def idle_under(idle, spans):
    """{name: idle seconds}: each gap of ``idle`` goes to the innermost span
    that covers its middle, or to ``none``."""
    parent, order = nest(spans)
    starts = [spans[i][0] for i in order]
    out = collections.Counter()
    for a, b in idle:
        mid, name = (a + b) / 2, "none"
        k = bisect.bisect_right(starts, mid) - 1
        i = order[k] if k >= 0 else None
        # the innermost span over ``mid`` is the latest-starting span before
        # it or one of that span's ancestors
        while i is not None:
            if spans[i][0] <= mid <= spans[i][1]:
                name = spans[i][2]
                break
            i = parent[i]
        out[name] += b - a
    return dict(out)


# ----------------------------------------------------------- model scopes
def scope_of(path):
    """The first model scope among the components of an ``op_name``."""
    for part in (path or "").split("/"):
        m = SCOPE_PART.match(part)
        if m:
            return m.group(1)
    return "other"


def program_at(modules):
    """A function from a time to the name of the program event (one
    device's ``XLA Modules`` line) that covers it, or None."""
    progs = sorted(modules)
    starts = [p[0] for p in progs]

    def at(t):
        k = bisect.bisect_right(starts, t) - 1
        return progs[k][2] if k >= 0 and progs[k][1] >= t else None
    return at


def extend(device_ops, modules, host_spans, window):
    """The keys this module adds, from ``trace.read``'s lists; an op may
    carry its ``op_name`` fourth, as ``read`` gives it."""
    lo, hi = window

    def inside(evs):
        return [e for e in evs if e[1] > lo and e[0] < hi]

    def clip(a, b):
        return max(a, lo), min(b, hi)

    scope_s = collections.Counter()
    module_scope_s = collections.defaultdict(collections.Counter)
    idle = []
    for dev, evs in device_ops.items():
        evs = inside(evs)
        at = program_at(modules.get(dev, []))
        idle.extend(trace.gaps([clip(e[0], e[1]) for e in evs], lo, hi))
        for e in evs:
            if CONTAINER.match(trace.op_name(e[2])):
                continue
            a, b = clip(e[0], e[1])
            scope = scope_of(e[3] if len(e) > 3 else "")
            scope_s[scope] += b - a
            prog = at((e[0] + e[1]) / 2)
            if prog is not None:
                module_scope_s[prog][scope] += b - a
    n_dev = max(len(device_ops), 1)
    host = [(*clip(a, b), n) for a, b, n in inside(host_spans)]
    self_s, count = host_times(host)
    return {
        "host_self_s": self_s,
        "host_n": count,
        "idle_under": {k: v / n_dev for k, v in idle_under(idle,
                                                           host).items()},
        "scope_s": {k: v / n_dev for k, v in scope_s.items()},
        "module_scope_s": {m: {k: v / n_dev for k, v in c.items()}
                           for m, c in module_scope_s.items()},
    }


# ------------------------------------------------ protobuf, by wire format
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """(field number, value) of one protobuf message: an int for varints
    and fixed-width fields, a memoryview for length-delimited ones."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not handled")
        yield num, val


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def hlo_op_names(data):
    """{program event name: {instruction name: op_name}} from the HLO each
    program leaves in a serialized XSpace's ``/host:metadata`` plane.

    XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map entries:
    value = 2), stat_metadata = 5; XEventMetadata: name = 2, stats = 5;
    XStatMetadata: id = 1, name = 2; XStat: metadata_id = 1, bytes = 6.
    HloProto.hlo_module = 1; HloModuleProto.computations = 3;
    HloComputationProto.instructions = 2; HloInstructionProto: name = 1,
    metadata = 7; OpMetadata.op_name = 2."""
    out = {}
    for num, plane in fields(data):
        if num != 1:
            continue
        parts = collections.defaultdict(list)
        for n, v in fields(plane):
            if n in (2, 4, 5):
                parts[n].append(v)
        if not parts[2] or _text(parts[2][0]) != "/host:metadata":
            continue
        stat_names = {}
        for entry in parts[5]:
            for n, v in fields(entry):
                if n == 2:
                    meta = dict(fields(v))
                    stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        for entry in parts[4]:
            for n, v in fields(entry):
                if n != 2:
                    continue
                name, protos = "", []
                for m, w in fields(v):
                    if m == 2:
                        name = _text(w)
                    elif m == 5:
                        stat = dict(fields(w))
                        if stat_names.get(stat.get(1)) == "Hlo Proto" \
                                and 6 in stat:
                            protos.append(stat[6])
                for proto in protos:
                    out[name] = _instruction_op_names(proto)
    return out


def _instruction_op_names(hlo_proto):
    names = {}
    for n, module in fields(hlo_proto):
        if n != 1:
            continue
        for c, comp in fields(module):
            if c != 3:
                continue
            for k, inst in fields(comp):
                if k != 2:
                    continue
                name = op_name = ""
                for f, v in fields(inst):
                    if f == 1:
                        name = _text(v)
                    elif f == 7:
                        for g, w in fields(v):
                            if g == 2:
                                op_name = _text(w)
                names[name] = op_name
    return names


# ------------------------------------------------------------------ reading
def read(path):
    """``trace.read``'s lists, each op with its ``op_name`` fourth ("" where
    the trace holds no HLO for its program)."""
    device_ops, modules, host, window = trace.read(path)
    with open(path, "rb") as f:
        hlo = hlo_op_names(f.read())
    named = {}
    for dev, evs in device_ops.items():
        at = program_at(modules.get(dev, []))
        named[dev] = [(a, b, n, hlo.get(at((a + b) / 2), {}).get(
            n.lstrip("%"), "")) for a, b, n in evs]
    return named, modules, host, window


def reduce(path):
    """``trace.summarize``'s result with ``extend``'s keys beside it."""
    device_ops, modules, host, window = read(path)
    out = trace.summarize({d: [e[:3] for e in evs]
                           for d, evs in device_ops.items()},
                          modules, host, window)
    out.update(extend(device_ops, modules, host, window))
    return out


def describe(logdir, ops=8):
    """The reduction of a kept trace, and a few op events with their stats
    and the ``op_name`` found for each."""
    from jax.profiler import ProfileData
    path = trace.find_xplane(logdir)
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for k, ev in enumerate(line.events):
                if k >= ops:
                    break
                print(f"{plane.name} {trace.op_name(ev.name)}: "
                      f"{list(ev.stats)}")
    summary = reduce(path)
    named = [e for evs in read(path)[0].values() for e in evs]
    print(f"ops with an op_name: {sum(1 for e in named if e[3])} of "
          f"{len(named)}")
    print(json.dumps({k: summary[k] for k in (
        "window_s", "busy_s", "host_self_s", "host_n", "idle_under",
        "scope_s", "module_scope_s")}, indent=1, sort_keys=True))


if __name__ == "__main__":
    describe(sys.argv[1])
