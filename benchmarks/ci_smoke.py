"""CI benchmark smoke gate: run the kernel and serving benchmarks on tiny
CPU configs, write a ``BENCH_ci.json`` artifact, and fail (exit 1) when any
benchmark's own PASS/FAIL verdict fails.

    PYTHONPATH=src python -m benchmarks.ci_smoke [--out BENCH_ci.json]

Gated verdicts:

* ``kernels/vmem_verdict``     — every Pallas tiling's analytic VMEM
  working set (including the fused score kernel) fits v5e's ~16 MB;
* ``serving/longtail_verdict`` — on the compact long-tail trace the
  chunked engine compiles strictly fewer programs than the bucketed
  baseline *and* cuts p95 TPOT;
* ``serving/decode_evict_verdict`` — on a long-generation paged-pool
  trace at equal KV bytes, decode-time eviction sweeps reclaim whole
  blocks mid-generation and lift peak concurrency, with every
  generation still completing at full length;
* ``prefix/reuse_verdict``     — on the Zipf shared-prefix trace the
  radix-trie prompt cache admits a fully cached prompt faster than one
  uncached chunk prefills, with >= 2x aggregate TTFT improvement;
* ``paged/admission_verdict``  — at an equal KV byte budget the paged
  block-pool engine admits >= 1.5x the concurrent requests of the dense
  engine on a mixed-length Zipf trace, p95 TTFT no worse (within the
  CPU dispatch-noise guard);
* ``kernels/paged_decode_verdict`` — the gather-free paged flash-decode
  path stays within the analytic HBM roofline budget (touched bytes
  <= ideal/0.85) at every (B, depth, block_size) point *and* measures
  strictly faster than the dense-gather oracle wherever depth >= 2k;
* ``sharded/scaling_verdict``  — on a forced 8-device host mesh the
  tensor-parallel paged engine's per-shard KV pool bytes scale exactly
  as total/model at model = {2, 4} and every width emits bit-identical
  tokens to single-device serving;
* ``lookahead/quality_verdict`` — the full learning loop (trace harvest
  -> gt_oracle distillation -> checkpoint -> serving load path): the
  trained predictor beats the untrained one on per-(layer, head) oracle
  kept-set overlap over held-out trace records, the distillation loss
  decreases, and the trained checkpoint serves end-to-end through
  ``ServingConfig.lkv_checkpoint``;
* ``obs/overhead_verdict``     — the observability layer is near-free
  and honest: obs-on serving throughput within 3% of obs-off on the CI
  long-tail trace, every admitted request closes a well-nested span
  tree in the emitted trace, and the streaming drift gauge matches the
  offline ``bench_lookahead_quality`` computation on the same records
  to float tolerance (also writes the ``BENCH_obs_metrics.json`` /
  ``BENCH_obs_trace.json`` artifacts).

The JSON artifact carries every reported benchmark row plus the verdict
map, so a red gate links straight to the number that moved.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from repro.common.compile_cache import enable_compile_cache

# every row name ending in ``_verdict`` gates the job
SUITES = ("benchmarks.bench_kernels", "benchmarks.bench_serving",
          "benchmarks.bench_prefix", "benchmarks.bench_paged",
          "benchmarks.bench_sharded", "benchmarks.bench_lookahead_quality",
          "benchmarks.bench_obs")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_ci.json")
    args = ap.parse_args()
    enable_compile_cache()

    rows: list[dict] = []
    verdicts: dict[str, str] = {}
    errors: list[str] = []

    def report(name, us, derived=""):
        rows.append({"name": name, "us_per_call": us, "derived": derived})
        if name.endswith("_verdict"):
            verdicts[name] = derived
        print(f"{name},{'' if us is None else f'{us:.1f}'},{derived}",
              flush=True)

    for mod_name in SUITES:
        t0 = time.time()
        try:
            mod = __import__(mod_name, fromlist=["run"])
            mod.run(report)
            report(f"{mod_name}/_suite_seconds", None,
                   f"{time.time() - t0:.1f}")
        except Exception as e:  # a crashed suite is a failed gate
            import traceback

            traceback.print_exc(file=sys.stderr)
            errors.append(f"{mod_name}: {e!r}")

    ok = bool(verdicts) and not errors and all(
        v == "pass" for v in verdicts.values())
    payload = {
        "pass": ok,
        "verdicts": verdicts,
        "errors": errors,
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"\nBENCH_ci: {'PASS' if ok else 'FAIL'} "
          f"verdicts={verdicts} errors={errors} -> {args.out}")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
