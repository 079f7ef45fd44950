"""Benchmark harness (deliverable (d)): one module per paper table/figure.
Prints `name,us_per_call,derived` CSV rows.

`--serving-workload mixed|shared|oversub|both` is passed through to
benchmarks.serving_bench (shared = the prefix-caching comparison, oversub =
the open-loop overload study: optimistic admission + preemption vs full
reservation); the mixed workload's rows include the packed-prefill TTFT
p50/p99 vs the B=1 chunked baseline, the per-(chunk x segments) AOT-bucket
dispatch counts, and the prefill variants seen-vs-declared check (new=0
after warmup). `--serving-family full|sliding|ssm|hybrid|all` adds the
per-family state-provider sweep; `--serving-seed` seeds every serving
workload generator (request lengths, arrival trace);
`--serving-trace-out PREFIX` writes each workload's request-lifecycle event
log to PREFIX.<workload>.jsonl (replayable via
repro.serving.telemetry.replay_jsonl). `--serving-kv-quant` adds the
quantized paged-KV rows: per-family tokens/s and state-KB/slot with the
pools fp32 vs int8+scales, the paged kernel's dequant overhead in
isolation, and peak resident sequences at a fixed pool byte budget."""
import argparse
import sys
import traceback

MODULES = [
    "benchmarks.table3_update_rules",     # Table 3: weight update rules
    "benchmarks.table4_workdepth",        # Table 4: layer W-D
    "benchmarks.table5_networks",         # Table 5 + §3.3.1 LeNet claim
    "benchmarks.table6_conv_algorithms",  # Table 6: conv algorithm W-D
    "benchmarks.fig6_collectives",        # Fig 6 / §2.5: allreduce algorithms
    "benchmarks.fig7_minibatch",          # Fig 7: minibatch-size effect
    "benchmarks.consistency_spectrum",    # §6.1 / Fig 28: staleness spectrum
    "benchmarks.compression_ratios",      # §6.3: quantization/sparsification
    "benchmarks.sec4_conv_measured",      # §4.3: conv algorithms, measured
    "benchmarks.sec64_sec65_meta",        # §6.4 consolidation + §6.5 meta-opt
    "benchmarks.kernels_bench",           # §4: layer computation kernels
    "benchmarks.serving_bench",           # §7 inference: engine vs static batch
    "benchmarks.roofline_summary",        # deliverable (g) roofline table
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--serving-workload",
                    choices=("mixed", "shared", "oversub", "both", "none"),
                    default="both", help="workload(s) for serving_bench")
    ap.add_argument("--serving-family",
                    choices=("full", "sliding", "ssm", "hybrid", "all"),
                    default=None,
                    help="per-family state-provider sweep for serving_bench")
    ap.add_argument("--serving-trace-out", default=None, metavar="PREFIX",
                    help="JSONL request-trace prefix for serving_bench")
    ap.add_argument("--serving-seed", type=int, default=0,
                    help="workload-generator seed for serving_bench")
    ap.add_argument("--serving-spec", action="store_true",
                    help="speculative-decoding rows for serving_bench "
                         "(per-family spec on/off tokens/s, acceptance rate, "
                         "tokens per verify step)")
    ap.add_argument("--serving-kv-quant", action="store_true",
                    help="quantized-KV rows for serving_bench (per-family "
                         "tokens/s and state-KB/slot fp32 vs int8, kernel "
                         "dequant overhead, fixed-budget pool capacity)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for mod_name in MODULES:
        kwargs = ({"workload": args.serving_workload,
                   "config_family": args.serving_family,
                   "trace_out": args.serving_trace_out,
                   "seed": args.serving_seed,
                   "spec": args.serving_spec,
                   "kv_quant": args.serving_kv_quant}
                  if mod_name == "benchmarks.serving_bench" else {})
        try:
            mod = __import__(mod_name, fromlist=["main"])
            mod.main(**kwargs)
        except Exception:
            failures += 1
            print(f"{mod_name},ERROR,", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
