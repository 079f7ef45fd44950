"""Device time of attention proper (the ``attn`` scope, forward and
backward, rematerialized forward included) per 1000 training tokens of the
steps in the traced window. Reads ``scope_s`` of ``perfbench/spans.py``."""


def read(obs, name):
    tr, tokens = obs.get("trace") or {}, obs.get("train_tokens")
    attn = (tr.get("scope_s") or {}).get("attn", 0.0)
    return 1e3 * attn / (tokens / 1000.0) if attn > 0 and tokens else None
