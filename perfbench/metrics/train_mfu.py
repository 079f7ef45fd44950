"""Model FLOP utilization of the traced training steps: forward and
backward operations per token (recomputation not counted) times the tokens
of the steps that ran in the traced window, over window x peak bf16 rate."""
from perfbench import model, peaks


def read(obs, name):
    tr, tokens = obs.get("trace"), obs.get("train_tokens")
    if not tr or not tokens:
        return None
    s = obs["sizes"]
    per = model.arch(s).train_flops_per_token(s, obs["seq"])
    return 100.0 * per * tokens / (tr["window_s"] * peaks.peaks(obs["kind"])
                                   ["bf16_flops"])
