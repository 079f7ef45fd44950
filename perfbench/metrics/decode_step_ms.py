"""Mean device time of one execution of the engine's decode program in the
traced window (its ``XLA Modules`` events)."""


def read(obs, name):
    tr = obs.get("trace")
    if not tr:
        return None
    keys = [k for k in tr["module_s"] if "decode_fn" in k]
    n = sum(tr["module_n"][k] for k in keys)
    return 1e3 * sum(tr["module_s"][k] for k in keys) / n if n else None
