"""Mean host time of one engine step in the traced window: the self time
of ``engine/step`` and of its ``engine/schedule`` and ``engine/emit``
children (what their own child spans, the device dispatches and the
transfers, leave uncovered), per ``engine/step`` span. Reads the
``host_self_s``/``host_n`` keys of ``perfbench/spans.py``; None where the
trace holds no ``engine/step`` span."""

PARTS = ("engine/step", "engine/schedule", "engine/emit")


def read(obs, name):
    tr = obs.get("trace") or {}
    self_s, n = tr.get("host_self_s"), (tr.get("host_n") or {})
    if not self_s or not n.get("engine/step"):
        return None
    return 1e3 * sum(self_s.get(k, 0.0) for k in PARTS) / n["engine/step"]
