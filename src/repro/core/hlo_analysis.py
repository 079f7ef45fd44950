"""Loop-aware analysis of optimized XLA HLO — the dry-run "profiler".

XLA's `compiled.cost_analysis()` counts a `while` body **once**, ignoring the
trip count — useless for scan-over-layers models. We therefore parse the
optimized HLO module text ourselves and compute, with trip-count
multiplication through nested loops:

  * `flops`            — 2·|out|·|contraction| per dot/convolution (MXU work)
  * `hbm_bytes`        — HBM traffic model: per top-level op (a fusion is one
                         kernel), operand bytes + result bytes
  * `collective_bytes` — result bytes of all-gather / all-reduce /
                         reduce-scatter / all-to-all / collective-permute

Trip counts are read from each while's condition region (`constant(N)` fed to
the loop compare). XLA's loop widening ("wide." regions hold k copies of the
body with trip N/k) stays consistent: trip × body-cost is invariant.

Validated in tests against analytical 6·N·D FLOPs and against unrolled
lowerings of the same program.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 0.5, "u4": 0.5, "f8e4m3fn": 1, "f8e5m2": 1, "token": 0,
}

_COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                   "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OP_RE = re.compile(r"^((?:\([^=]*\))|(?:[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?))\s+"
                    r"([\w\-]+)\(")
_CALL_RE = re.compile(r"(?:calls|body|condition|to_apply|branch_computations)="
                      r"\{?%?([\w.\-]+(?:,\s*%[\w.\-]+)*)\}?")


def _shape_list_bytes(text):
    """Total bytes of all shape tokens in `text`."""
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_elems(shape_text):
    m = _SHAPE_RE.search(shape_text)
    if not m:
        return 0
    dims = m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


@dataclass
class Instr:
    name: str
    result: str          # result shape text (may be a tuple)
    op: str
    rest: str            # full rhs text

    @property
    def result_bytes(self):
        return _shape_list_bytes(self.result)


@dataclass
class Computation:
    name: str
    instrs: list = field(default_factory=list)
    by_name: dict = field(default_factory=dict)


def parse_module(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        if not line.startswith(" ") and "{" in line and ("%" in line or line.startswith("ENTRY")):
            # computation header: `%name (params) -> shape {` or `ENTRY %name ...`
            m = re.search(r"%([\w.\-]+)\s*\(", line)
            if m:
                cur = Computation(m.group(1))
                comps[cur.name] = cur
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        dm = _DEF_RE.match(line)
        if not dm:
            continue
        name, rhs = dm.group(1), dm.group(2)
        om = _OP_RE.match(rhs)
        if om:
            result, op = om.group(1), om.group(2)
        else:
            # e.g. `%p = (tuple...) parameter(0)` handled above; fallback
            result, op = rhs.split(")")[0] + ")", "unknown"
            w = re.search(r"\)\s*([\w\-]+)\(", rhs)
            if w:
                op = w.group(1)
        ins = Instr(name, result, op, rhs)
        cur.instrs.append(ins)
        cur.by_name[name] = ins
    return comps


def _trip_count(comps, cond_name) -> int:
    cond = comps.get(cond_name)
    if cond is None:
        return 1
    consts = []
    for ins in cond.instrs:
        m = re.search(r"constant\((-?\d+)\)", ins.rest)
        if m:
            consts.append(int(m.group(1)))
        # compare may live in a fusion region
        cm = _CALL_RE.search(ins.rest)
        if cm and ins.op == "fusion":
            sub = comps.get(cm.group(1).split(",")[0].strip().lstrip("%"))
            if sub:
                for si in sub.instrs:
                    m2 = re.search(r"constant\((-?\d+)\)", si.rest)
                    if m2:
                        consts.append(int(m2.group(1)))
    consts = [c for c in consts if c > 0]
    return max(consts) if consts else 1


def _dot_flops(ins: Instr, comp: Computation, comps) -> float:
    """2 × |output| × |contracting dims| (+ batch handled via output size)."""
    out_elems = _shape_elems(ins.result)
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.rest)
    if not m:
        return 2.0 * out_elems
    cdims = [int(d) for d in m.group(1).split(",") if d != ""]
    # lhs operand shape: first %name inside parens
    am = re.search(r"\(\s*%([\w.\-]+)", ins.rest)
    contract = 1
    if am:
        op = comp.by_name.get(am.group(1))
        if op is not None:
            sm = _SHAPE_RE.search(op.result)
            if sm and sm.group(2):
                dims = [int(d) for d in sm.group(2).split(",")]
                for c in cdims:
                    if c < len(dims):
                        contract *= dims[c]
    return 2.0 * out_elems * contract


def _conv_flops(ins: Instr) -> float:
    out_elems = _shape_elems(ins.result)
    m = re.search(r"window=\{size=([0-9x]+)", ins.rest)
    k = 1
    if m:
        for d in m.group(1).split("x"):
            k *= int(d)
    return 2.0 * out_elems * k


_cache = {}


def analyze_computation(comps, name, depth=0) -> dict:
    """Recursive (memoized) cost of one computation."""
    key = name
    if key in _cache:
        return _cache[key]
    comp = comps.get(name)
    out = {"flops": 0.0, "hbm_bytes": 0.0,
           "collective_bytes": defaultdict(float), "collective_counts": defaultdict(float)}
    if comp is None or depth > 60:
        return out
    _cache[key] = out  # pre-insert to break cycles
    for ins in comp.instrs:
        op = ins.op
        if op in ("parameter", "constant", "get-tuple-element", "tuple",
                  "bitcast", "unknown", "after-all"):
            continue
        callees = _CALL_RE.findall(ins.rest)
        if op == "while":
            body = cond = None
            bm = re.search(r"body=%?([\w.\-]+)", ins.rest)
            cm = re.search(r"condition=%?([\w.\-]+)", ins.rest)
            if bm:
                body = bm.group(1)
            if cm:
                cond = cm.group(1)
            trip = _trip_count(comps, cond) if cond else 1
            sub = analyze_computation(comps, body, depth + 1) if body else out
            out["flops"] += trip * sub["flops"]
            out["hbm_bytes"] += trip * sub["hbm_bytes"]
            for k, v in sub["collective_bytes"].items():
                out["collective_bytes"][k] += trip * v
                out["collective_counts"][k] += trip * sub["collective_counts"][k]
            continue
        if op in ("call", "conditional", "async-start"):
            for group in callees:
                for cal in group.split(","):
                    sub = analyze_computation(comps, cal.strip().lstrip("%"), depth + 1)
                    out["flops"] += sub["flops"]
                    out["hbm_bytes"] += sub["hbm_bytes"]
                    for k, v in sub["collective_bytes"].items():
                        out["collective_bytes"][k] += v
                        out["collective_counts"][k] += sub["collective_counts"][k]
            continue
        if op == "fusion":
            # one kernel: HBM traffic = operands + result; flops from inside.
            # In-place loop fusions (dynamic-update-slice root, XLA aliases
            # the buffer) touch only the updated slice, not the whole buffer:
            # count the non-buffer operands + 2x the smallest-operand proxy.
            operand_names = re.findall(r"%([\w.\-]+)", ins.rest.split("),")[0])
            operand_sizes = [comp.by_name[on].result_bytes
                             for on in operand_names if on in comp.by_name]
            if "dynamic_update_slice" in ins.rest or "DynamicUpdateSlice" in ins.rest:
                big = max(operand_sizes, default=0.0)
                if ins.result_bytes >= big > 0:  # buffer aliased through
                    out["hbm_bytes"] += 2.0 * max(sum(operand_sizes) - big,
                                                  0.05 * big)
                else:
                    out["hbm_bytes"] += sum(operand_sizes) + ins.result_bytes
            else:
                out["hbm_bytes"] += sum(operand_sizes) + ins.result_bytes
            cm2 = re.search(r"calls=%?([\w.\-]+)", ins.rest)
            if cm2:
                sub = analyze_computation(comps, cm2.group(1), depth + 1)
                out["flops"] += sub["flops"]
                for k, v in sub["collective_bytes"].items():
                    out["collective_bytes"][k] += v
                    out["collective_counts"][k] += sub["collective_counts"][k]
            continue

        # plain op
        base = None
        for c in _COLLECTIVE_OPS:
            if op == c or op == c + "-start":
                base = c
                break
        if base:
            out["collective_bytes"][base] += ins.result_bytes
            out["collective_counts"][base] += 1
        if op in ("dot",):
            out["flops"] += _dot_flops(ins, comp, comps)
        elif op == "convolution":
            out["flops"] += _conv_flops(ins)
        elif op == "custom-call" and ("matmul" in ins.rest or "dot" in ins.rest):
            out["flops"] += 2.0 * _shape_elems(ins.result)  # conservative
        # HBM traffic for non-fusion compute ops. Sliced reads/writes touch
        # only the slice, not the full operand (scan weight indexing would
        # otherwise count the whole stacked tensor per trip).
        if op in ("dynamic-slice", "slice", "gather", "broadcast", "reshape",
                  "transpose", "copy"):
            out["hbm_bytes"] += 2.0 * ins.result_bytes
        elif op in ("dynamic-update-slice", "scatter"):
            operand_names = re.findall(r"%([\w.\-]+)", ins.rest)
            upd = 0.0
            if len(operand_names) >= 2 and operand_names[1] in comp.by_name:
                upd = comp.by_name[operand_names[1]].result_bytes
            out["hbm_bytes"] += 2.0 * (upd or ins.result_bytes)
        elif op not in ("copy-start", "copy-done"):
            operand_names = re.findall(r"%([\w.\-]+)", ins.rest)
            operand_bytes = sum(
                comp.by_name[on].result_bytes for on in operand_names
                if on in comp.by_name)
            out["hbm_bytes"] += operand_bytes + ins.result_bytes
    return out


def top_ops(text: str, n=15, metric="hbm_bytes") -> list:
    """Trip-weighted per-op cost ranking — the dry-run 'profile' used by the
    §Perf hypothesis loop. Returns [(cost, op, name, metadata_hint)]."""
    _cache.clear()
    comps = parse_module(text)
    m = re.search(r"ENTRY\s+%?([\w.\-]+)", text)
    entry = m.group(1) if m else None
    rows = []

    def visit(name, mult, depth=0):
        comp = comps.get(name)
        if comp is None or depth > 60:
            return
        for ins in comp.instrs:
            op = ins.op
            if op in ("parameter", "constant", "get-tuple-element", "tuple",
                      "bitcast", "unknown", "after-all"):
                continue
            if op == "while":
                bm = re.search(r"body=%?([\w.\-]+)", ins.rest)
                cm = re.search(r"condition=%?([\w.\-]+)", ins.rest)
                trip = _trip_count(comps, cm.group(1)) if cm else 1
                if bm:
                    visit(bm.group(1), mult * trip, depth + 1)
                continue
            if op in ("call", "conditional"):
                for group in _CALL_RE.findall(ins.rest):
                    for cal in group.split(","):
                        visit(cal.strip().lstrip("%"), mult, depth + 1)
                continue
            if metric == "flops":
                cost = _dot_flops(ins, comp, comps) if op == "dot" else (
                    _conv_flops(ins) if op == "convolution" else 0.0)
                if op == "fusion":
                    cm2 = re.search(r"calls=%?([\w.\-]+)", ins.rest)
                    if cm2:
                        cost = analyze_computation(comps, cm2.group(1))["flops"]
            else:
                if op == "fusion":
                    operand_names = re.findall(r"%([\w.\-]+)",
                                               ins.rest.split("),")[0])
                    sizes = [comp.by_name[o].result_bytes for o in operand_names
                             if o in comp.by_name]
                    if ("dynamic_update_slice" in ins.rest
                            and ins.result_bytes >= max(sizes, default=0) > 0):
                        cost = 2.0 * max(sum(sizes) - max(sizes),
                                         0.05 * max(sizes))
                    else:
                        cost = ins.result_bytes + sum(sizes)
                elif op in ("dynamic-slice", "slice", "gather", "broadcast",
                            "reshape", "transpose", "copy"):
                    cost = 2.0 * ins.result_bytes
                else:
                    operand_names = re.findall(r"%([\w.\-]+)", ins.rest)
                    cost = ins.result_bytes + sum(
                        comp.by_name[o].result_bytes for o in operand_names
                        if o in comp.by_name)
            if cost:
                hint = ""
                hm = re.search(r'op_name="([^"]*)"', ins.rest)
                if hm:
                    hint = hm.group(1)[-90:]
                rows.append((cost * mult, op, ins.name,
                             _SHAPE_RE.search(ins.result).group(0)
                             if _SHAPE_RE.search(ins.result) else "", hint))

    if entry:
        visit(entry, 1.0)
    rows.sort(key=lambda r: -r[0])
    return rows[:n]


def analyze_hlo_text(text: str) -> dict:
    """Loop-aware module cost. Entry = the computation named in `ENTRY`."""
    _cache.clear()
    comps = parse_module(text)
    entry = None
    m = re.search(r"ENTRY\s+%?([\w.\-]+)", text)
    if m:
        entry = m.group(1)
    if entry not in comps:
        # fall back: the computation with the most instructions
        entry = max(comps, key=lambda c: len(comps[c].instrs)) if comps else None
    if entry is None:
        return {"flops": 0.0, "hbm_bytes": 0.0, "collective_bytes": {}, "total_collective_bytes": 0.0}
    res = analyze_computation(comps, entry)
    res = {
        "flops": res["flops"],
        "hbm_bytes": res["hbm_bytes"],
        "collective_bytes": dict(res["collective_bytes"]),
        "collective_counts": dict(res["collective_counts"]),
    }
    res["total_collective_bytes"] = sum(res["collective_bytes"].values())
    return res


# ------------------------------------------------------------- jax interface
def cost_analysis_dict(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    return dict(ca) if ca else {}


def memory_analysis_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for k in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "temp_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def analyze_compiled(lowered, compiled) -> dict:
    ca = cost_analysis_dict(compiled)
    try:
        text = compiled.as_text()
    except Exception:
        text = lowered.as_text()
    loop_aware = analyze_hlo_text(text)
    return {
        "xla_cost_flops": float(ca.get("flops", 0.0)),
        "xla_cost_bytes": float(ca.get("bytes accessed", 0.0)),
        "flops": loop_aware["flops"],
        "hbm_bytes": loop_aware["hbm_bytes"],
        "collectives": {
            "total": loop_aware["total_collective_bytes"],
            "by_op": loop_aware["collective_bytes"],
            "counts": loop_aware["collective_counts"],
        },
        "memory": memory_analysis_dict(compiled),
    }


def collective_bytes(hlo_text: str, per_op: bool = False):
    """Loop-aware collective byte count from HLO text."""
    res = analyze_hlo_text(hlo_text)
    out = {"total": res["total_collective_bytes"], "by_op": res["collective_bytes"],
           "counts": res["collective_counts"]}
    return out if per_op else out["total"]
