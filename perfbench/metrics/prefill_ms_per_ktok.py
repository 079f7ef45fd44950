"""Device time of the engine's prefill programs in the traced window per
1000 prompt tokens they computed (padding not counted)."""


def read(obs, name):
    tr, chunks = obs.get("trace"), obs.get("prefill")
    if not tr or not chunks:
        return None
    tokens = sum(v for _, v in chunks)
    dev = sum(s for k, s in tr["module_s"].items() if "prefill_fn" in k)
    return 1e3 * dev / (tokens / 1000.0) if dev > 0 else None
