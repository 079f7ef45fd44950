"""A cell at toy sizes for the CPU tests: the same files' shapes, with a
2-layer decoder of width 64 and a pool of a few blocks."""
import copy
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

SPEC = {
    "name": "tiny", "arch": "decoder", "registry": "phi4-mini-3.8b",
    "reference": "decoder",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
}

# The toy decoder's served tokens lie below the f32 reference's best by at
# most 0.00095 on seeds 1-3, the fp8 control's by at least 0.0086 (CPU); its
# comparison's limit sits between them. The cells' own limits are set from
# chip readings at full size (PERF.md).
GAP_LIMIT = 0.003

ENGINE = {"block_size": 16, "num_blocks": 64, "max_slots": 4,
          "max_blocks_per_seq": 8, "prefill_chunk": 32,
          "prefills_per_step": 2}


def traffic(driver):
    """The committed traffic file of that driver, cut to toy lengths."""
    name = {"open_loop": "chat", "backlog": "offline",
            "train_steps": "train4k"}[driver]
    with open(os.path.join(ROOT, "perfbench", "traffic", f"{name}.json")) as f:
        t = copy.deepcopy(json.load(f))
    if driver == "train_steps":
        # the toy's own limits: its program read grad 0.0017-0.0027 and
        # change 0.0020-0.0021, its fp8 control 0.037-0.046 and
        # 0.0052-0.0066, half a batch 0.096-0.20 and 0.20-0.21 (CPU)
        t.update(batch=2, seq=64,
                 check={"grad_norm_gap": 0.01, "delta_norm_gap": 0.004})
        return t
    t.update(warm_in_s=0.5, engine=dict(ENGINE))
    t["prompt"] = {"median": 24, "sigma": 0.8, "min": 4, "max": 64}
    t["output"] = {"median": 8, "sigma": 0.5, "min": 2, "max": 32}
    t["check"] = dict(t["check"], tokens=40, requests=3)
    if driver == "open_loop":
        t["rate_rps"] = 8.0
    else:
        t["backlog"] = 8
    return t


def cell(driver, spec=None):
    """A cell dict as ``bench.load_cell`` returns it."""
    from perfbench import bench
    man = bench.manifest()
    e2e = {"open_loop": ["ttft_p75_ms", "itl_p95_ms"],
           "backlog": ["output_tok_s"],
           "train_steps": ["train_tok_s"]}[driver] + ["setup_s"]
    ends = [m for m in man["end_to_end"] if m["name"] in e2e]
    moved = {m["name"] for m in ends}
    if spec is None:
        spec = dict(SPEC, tie_word_embeddings=driver != "train_steps")
    return {"name": f"tiny.{driver}", "config": "tiny",
            "chips": 1, "why": "toy sizes for the CPU tests",
            "spec": spec, "traffic": traffic(driver),
            "end_to_end": ends,
            "per_layer": [m for m in man["per_layer"] if m["moves"] in moved],
            "run_seconds": 2}
