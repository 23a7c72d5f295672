"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Wall-clock numbers are CPU
smoke-scale (trend validation, like the paper's 10-step averages);
full-scale rows are analytic from the dry-run artifacts (results/dryrun).

Usage: PYTHONPATH=src python -m benchmarks.run [--only table3]
"""
import argparse
import sys
import traceback

from benchmarks import (bench_decode, bench_latency, fig6_serving,
                        fig11_gemm, fig13_collectives, table2_frameworks,
                        table3_techniques, table5_modulewise,
                        table8_flashattention, table9_finetuning)
from repro.launch.jaxcache import use_compile_cache

SUITES = {
    "table2": table2_frameworks.run,      # Megatron vs DeepSpeed
    "table3": table3_techniques.run,      # optimization matrix
    "table5": table5_modulewise.run,      # phase + module breakdown
    "table8": table8_flashattention.run,  # flash vs naive attention
    "table9": table9_finetuning.run,      # LoRA/QLoRA fine-tuning
    "fig6": fig6_serving.run,             # serving throughput/latency
    "bench_decode": bench_decode.run,     # legacy vs fused decode tok/s
    "bench_latency": bench_latency.run,   # Poisson TTFT/TPOT percentiles
    "fig11": fig11_gemm.run,              # GEMM alignment sweep
    "fig13": fig13_collectives.run,       # collectives + memcpy
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--spec-depth", type=int, default=8,
                    help="max speculation depth K for bench_decode's "
                         "speculative scenarios")
    args = ap.parse_args()
    use_compile_cache()
    suite_kw = {"bench_decode": {"spec_depth": args.spec_depth}}
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in SUITES.items():
        if args.only and args.only != name:
            continue
        try:
            fn(**suite_kw.get(name, {}))
        except Exception:
            failures += 1
            print(f"{name}/ERROR,0,{traceback.format_exc(limit=1)!r}",
                  file=sys.stderr)
            traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
