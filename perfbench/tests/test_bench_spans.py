"""The reduction of the program's own instrumentation on a small recorded
trace: host self time of nested spans, idle time under the innermost span,
device time per model scope (loops and calls left out), the HLO op names a
serialized trace carries, and the readers of the metrics built on them."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import bench, spans, trace  # noqa: E402

# A recorded window of 1.0 s on one chip (seconds): two engine steps, each
# a decode program, then a prefill; the host's nested spans around them.
DEC = "jit(decode_fn)/while/body/closed_call"
OPS = {"/device:TPU:0": [
    (0.06, 0.20, "%while.5", "jit(decode_fn)/while"),          # container
    (0.06, 0.10, "%fusion.1", f"{DEC}/attn/dot_general"),
    (0.10, 0.14, "%paged_attention.9",
     f"{DEC}/attn/jit(paged_attention)/pallas_call"),
    (0.14, 0.16, "%fusion.2", f"{DEC}/kv_write/scatter"),
    (0.16, 0.19, "%fusion.3", f"{DEC}/mlp/dot_general"),
    (0.19, 0.20, "%copy.4", ""),                                # no op_name
    (0.20, 0.22, "%iota_reduce_fusion", "jit(decode_fn)/head/reduce"),
    (0.40, 0.50, "%fusion.1", f"{DEC}/attn/dot_general"),
    (0.70, 0.85, "%fusion.7",
     "jit(prefill_fn)/while/body/transpose(jvp(attn))/dot_general"),
    (0.85, 1.10, "%fusion.8", "jit(prefill_fn)/while/body/mlp/mul"),
]}
MODULES = {"/device:TPU:0": [
    (0.06, 0.22, "jit_decode_fn(1)"), (0.40, 0.50, "jit_decode_fn(1)"),
    (0.70, 1.10, "jit_prefill_fn(2)"),
]}
HOST = [
    (0.00, 0.30, "engine/step"),
    (0.00, 0.04, "engine/schedule"),
    (0.04, 0.05, "engine/decode"),
    (0.05, 0.30, "engine/emit"),
    (0.23, 0.29, "engine/sync"),              # inside emit
    (0.30, 0.60, "engine/step"),
    (0.30, 0.32, "engine/schedule"),
    (0.32, 0.34, "engine/decode"),
    (0.34, 0.58, "engine/emit"),
    (0.60, 0.70, "bench/read_tokens"),
]
WINDOW = (0.0, 1.0)


def summary():
    out = trace.summarize({d: [e[:3] for e in evs] for d, evs in OPS.items()},
                          MODULES, HOST, WINDOW)
    out.update(spans.extend(OPS, MODULES, HOST, WINDOW))
    return out


def test_nesting_and_self_time():
    parent, order = spans.nest(HOST)
    assert parent[0] is None and parent[1] == 0 and parent[4] == 3
    assert parent[9] is None
    assert [HOST[i][0] for i in order] == sorted(h[0] for h in HOST)
    s = summary()
    self_s, n = s["host_self_s"], s["host_n"]
    # steps: 0.30 - (0.04 + 0.01 + 0.25), 0.30 - (0.02 + 0.02 + 0.24)
    assert self_s["engine/step"] == pytest.approx(0.0 + 0.02)
    assert self_s["engine/emit"] == pytest.approx(0.25 - 0.06 + 0.24)
    assert self_s["engine/sync"] == pytest.approx(0.06)
    assert n == {"engine/step": 2, "engine/schedule": 2, "engine/decode": 2,
                 "engine/emit": 2, "engine/sync": 1, "bench/read_tokens": 1}


def test_idle_time_goes_to_the_innermost_span():
    s = summary()
    # idle: [0, 0.06] (mid 0.03, schedule), [0.22, 0.40] (mid 0.31, the
    # second step's schedule), [0.50, 0.70] (mid 0.60: read_tokens starts
    # there, so it is innermost)
    assert s["idle_under"] == pytest.approx({
        "engine/schedule": 0.06 + 0.18, "bench/read_tokens": 0.20})
    gap = spans.idle_under([(0.25, 0.27), (0.59, 0.595), (2.0, 3.0)], HOST)
    assert gap == pytest.approx({"engine/sync": 0.02, "engine/step": 0.005,
                                 "none": 1.0})


def test_scope_time_leaves_out_containers():
    s = summary()
    assert s["scope_s"] == pytest.approx({
        "attn": 0.04 + 0.04 + 0.10 + 0.15, "kv_write": 0.02, "mlp": 0.03
        + 0.15, "head": 0.02, "other": 0.01})
    per = s["module_scope_s"]
    assert per["jit_decode_fn(1)"] == pytest.approx(
        {"attn": 0.18, "kv_write": 0.02, "mlp": 0.03, "head": 0.02,
         "other": 0.01})
    # the prefill's last op is clipped at the window's end
    assert per["jit_prefill_fn(2)"] == pytest.approx({"attn": 0.15,
                                                      "mlp": 0.15})


@pytest.mark.parametrize("path,scope", [
    ("jit(f)/while/body/closed_call/attn/tanh", "attn"),
    ("jit(train_step)/transpose(jvp(head))/reduce", "head"),
    ("jit(f)/kv_write/attn/x", "kv_write"),
    ("jit(train_step)/optimizer/mul", "optimizer"),
    ("state['params']['blocks']['l0']['attn']['wq']", "other"),
    ("jit(f)/attention/dot", "other"),
    ("", "other"),
])
def test_scope_of(path, scope):
    assert spans.scope_of(path) == scope


def test_summarize_keys_unchanged():
    """The added keys sit beside ``summarize``'s, whose values stay what the
    same trace gives without them."""
    plain = trace.summarize({d: [e[:3] for e in evs]
                             for d, evs in OPS.items()}, MODULES, HOST,
                            WINDOW)
    s = summary()
    added = {"host_self_s", "host_n", "idle_under", "scope_s",
             "module_scope_s"}
    assert set(s) == set(plain) | added and not added & set(plain)
    assert {k: s[k] for k in plain} == plain


# -------------------------------------------------- serialized trace (wire)
def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*pairs):
    return b"".join(_field(n, v) for n, v in pairs)


def _xspace(program, instructions):
    inst = [_msg((1, name), (7, _msg((1, "op"), (2, op))))
            for name, op in instructions]
    hlo = _msg((1, _msg((1, "m"), *[(3, _msg((1, "c"), *[(2, i)
                                                         for i in inst]))])))
    event_meta = _msg((1, 9), (2, program),
                      (5, _msg((1, 3), (6, hlo))))
    plane = _msg((1, 5), (2, "/host:metadata"),
                 (4, _msg((1, 9), (2, event_meta))),
                 (5, _msg((1, 3), (2, _msg((1, 3), (2, "Hlo Proto"))))))
    other = _msg((2, "/device:TPU:0"), (3, _msg((2, "XLA Ops"))))
    return _msg((1, other), (1, plane))


def test_hlo_op_names_from_the_metadata_plane():
    data = _xspace("jit_decode_fn(7)", [
        ("fusion.1", f"{DEC}/attn/dot_general"), ("copy.2", "")])
    assert spans.hlo_op_names(data) == {"jit_decode_fn(7)": {
        "fusion.1": f"{DEC}/attn/dot_general", "copy.2": ""}}
    assert spans.hlo_op_names(_msg((1, _msg((2, "/host:CPU"))))) == {}


def test_program_at():
    at = spans.program_at(MODULES["/device:TPU:0"])
    assert at(0.1) == "jit_decode_fn(1)" and at(0.8) == "jit_prefill_fn(2)"
    assert at(0.3) is None and at(0.0) is None


# ----------------------------------------------------------------- readers
def _read(name, obs):
    return bench.load_module("metrics", name).read(obs, name)


def test_metric_readers():
    s = summary()
    # (self: step 0.02 + schedule 0.06 + emit 0.43) / 2 steps
    assert _read("host_step_ms.online", {"trace": s}) == pytest.approx(
        1e3 * (0.02 + 0.06 + 0.43) / 2)
    assert _read("idle_engine_pct.offline", {"trace": s}) == pytest.approx(
        100 * 0.24)
    obs = {"trace": s, "prefill": [(0, 300), (300, 200)]}
    assert _read("prefill_attn_ms_per_ktok", obs) == pytest.approx(
        1e3 * 0.15 / 0.5)
    assert _read("attn_ms_per_ktok.train",
                 {"trace": s, "train_tokens": 4000}) == pytest.approx(
        1e3 * 0.33 / 4)


@pytest.mark.parametrize("name", ["host_step_ms.online",
                                  "idle_engine_pct.online",
                                  "prefill_attn_ms_per_ktok",
                                  "attn_ms_per_ktok.train"])
def test_readers_find_nothing_in_a_plain_summary(name):
    """A trace reduced without this module's keys (or a program without
    the spans and scopes) gives no number, and no error."""
    plain = trace.summarize({d: [e[:3] for e in evs]
                             for d, evs in OPS.items()}, MODULES, [], WINDOW)
    obs = {"trace": plain, "prefill": [(0, 300)], "train_tokens": 4000}
    assert _read(name, obs) is None
    assert _read(name, {"trace": None}) is None
