"""The paged attention kernel's share of its roofline in the traced window.

Least time = the larger of operations / peak bf16 rate and bytes / HBM
bandwidth, with the work counted from the live lengths of every decode
step (the architecture's ``paged_attention_work``), never from the
kernel's grid; over the summed device time of the kernel's ops. Decode steps here are bound by
bytes: a query per head against each live token's K and V.
"""
import re

from perfbench import model, peaks

KERNEL = re.compile(r"^%?paged_attention(\.\d+)?$")


def kernel_seconds(trace):
    return sum(s for k, s in trace["op_s"].items() if KERNEL.search(k))


def read(obs, name):
    tr, steps = obs.get("trace"), obs.get("decode_lens")
    if not tr or not steps:
        return None
    t = kernel_seconds(tr)
    if t <= 0:
        return None
    pk = peaks.peaks(obs["kind"])
    s = obs["sizes"]
    work = model.arch(s).paged_attention_work
    ops = bytes_ = 0
    for lens in steps:
        o, b = work(s, lens)
        ops += o
        bytes_ += b
    least = max(ops / pk["bf16_flops"], bytes_ / pk["hbm_bytes_s"])
    return 100.0 * least / t
