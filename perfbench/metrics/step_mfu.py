"""Model operations of every token the engine computed in the traced
window (decode tokens through the head, prefill tokens causally, the last
of each chunk through the head), over window x peak bf16 rate."""
from perfbench import model, peaks


def read(obs, name):
    tr = obs.get("trace")
    steps, chunks = obs.get("decode_lens"), obs.get("prefill")
    if not tr or (not steps and not chunks):
        return None
    s = obs["sizes"]
    arch = model.arch(s)
    ops = sum(arch.token_flops(s, n) for lens in steps or [] for n in lens)
    ops += sum(arch.prefill_flops(s, st, v) for st, v in chunks or [])
    return 100.0 * ops / (tr["window_s"] * peaks.peaks(obs["kind"])
                          ["bf16_flops"])
