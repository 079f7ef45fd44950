"""Device time of attention proper (the ``attn`` scope: gather, masked
softmax and context) in the engine's prefill programs in the traced
window, per 1000 prompt tokens they computed (padding not counted, as in
``prefill_ms_per_ktok``). Reads ``module_scope_s`` of
``perfbench/spans.py``."""


def read(obs, name):
    tr, chunks = obs.get("trace") or {}, obs.get("prefill")
    per = tr.get("module_scope_s") or {}
    dev = sum(s.get("attn", 0.0) for k, s in per.items() if "prefill_fn" in k)
    tokens = sum(v for _, v in chunks or [])
    return 1e3 * dev / (tokens / 1000.0) if dev > 0 and tokens else None
