"""Benchmark orchestrator — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only accuracy,ttft,...]

Prints ``name,us_per_call,derived`` CSV lines (us_per_call empty for
quality/derived metrics).
"""

from __future__ import annotations

import argparse
import sys
import time

from repro.common.compile_cache import enable_compile_cache

SUITES = {
    "accuracy": "benchmarks.bench_accuracy",        # Fig 4 / Tables 9-14
    "ttft": "benchmarks.bench_ttft",                # Table 3/15, Fig 3
    "ablation": "benchmarks.bench_ablation",        # Table 5
    "temperature": "benchmarks.bench_temperature",  # Tables 4 + 8
    "context": "benchmarks.bench_context_scaling",  # RULER figs
    "longform": "benchmarks.bench_longform",        # Fig 5 (LongProc proxy)
    "roofline": "benchmarks.bench_roofline",        # §Roofline (dry-run)
    "kernels": "benchmarks.bench_kernels",          # kernel micro-bench
    "serving": "benchmarks.bench_serving",          # continuous vs lockstep
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated suite names (default: all)")
    args = ap.parse_args()
    enable_compile_cache()
    names = args.only.split(",") if args.only else list(SUITES)

    print("name,us_per_call,derived")

    def report(name, us, derived=""):
        us_s = f"{us:.1f}" if us is not None else ""
        print(f"{name},{us_s},{derived}", flush=True)

    failures = []
    for name in names:
        mod_name = SUITES[name]
        t0 = time.time()
        try:
            mod = __import__(mod_name, fromlist=["run"])
            mod.run(report)
            report(f"{name}/_suite_seconds", None, f"{time.time()-t0:.1f}")
        except Exception as e:  # keep the harness going
            import traceback

            traceback.print_exc(file=sys.stderr)
            failures.append((name, repr(e)))
            report(f"{name}/_suite_error", None, repr(e))
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
