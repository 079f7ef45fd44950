"""Share of the traced window in which the device sat idle while the
engine's host loop ran: 100 * the idle time whose middle lies under an
``engine/*`` span (the ``idle_under`` key of ``perfbench/spans.py``) over
the window. None where no idle time lies under one."""


def read(obs, name):
    tr = obs.get("trace") or {}
    under = tr.get("idle_under") or {}
    idle = sum(v for k, v in under.items() if k.startswith("engine/"))
    if not idle or tr.get("window_s", 0) <= 0:
        return None
    return 100.0 * idle / tr["window_s"]
