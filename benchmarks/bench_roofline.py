"""§Roofline table generator (deliverable g): reads the dry-run JSONs in
experiments/dryrun/ and prints the per-(arch × shape × mesh) roofline terms,
dominant bottleneck, and MODEL_FLOPS/HLO_FLOPs ratio.  Also emits the
markdown table consumed by EXPERIMENTS.md, plus the *measured* paged
flash-decode roofline rows (``bench_kernels.paged_decode_rows``) the
nightly sweep archives."""

from __future__ import annotations

import glob
import json
import os

from repro.common.compile_cache import enable_compile_cache

DRYRUN_DIR = os.environ.get("REPRO_DRYRUN_DIR", "experiments/dryrun")


def paged_decode_table() -> str:
    """Markdown table of the paged flash-decode budget: measured kernel-path
    vs gather wall time and the analytic achieved-fraction-of-roofline at
    each (B, depth, block_size) point."""
    from benchmarks.bench_kernels import ROOFLINE_FRAC, paged_decode_rows

    rows = [
        "| B | depth | block | path | path (µs) | gather (µs) | speedup "
        "| roofline frac | budget |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in paged_decode_rows():
        rows.append(
            f"| {r['B']} | {r['depth']} | {r['block_size']} | {r['path']} "
            f"| {r['us']:.0f} | {r['gather_us']:.0f} "
            f"| {r['gather_us']/r['us']:.2f}× "
            f"| {r['roofline_frac']:.3f} "
            f"| {'ok' if r['roofline_frac'] >= ROOFLINE_FRAC else 'MISS'} |"
        )
    return "\n".join(rows)


def load_results(mesh: str | None = None) -> list[dict]:
    out = []
    for p in sorted(glob.glob(os.path.join(DRYRUN_DIR, "*.json"))):
        with open(p) as f:
            r = json.load(f)
        if mesh and r.get("mesh") != mesh:
            continue
        out.append(r)
    return out


def markdown_table(results: list[dict]) -> str:
    rows = [
        "| arch | shape | mesh | compute (ms) | memory (ms) | collective (ms)"
        " | bottleneck | useful-FLOP ratio | peak MB/dev |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in results:
        if r.get("status") == "skipped":
            rows.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                        f"— | — | — | skipped | — | — |")
            continue
        if r.get("status") != "ok":
            rows.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                        f"— | — | — | ERROR | — | — |")
            continue
        rl = r["roofline"]
        peak = r["memory"].get("peak_memory_in_bytes")
        peak_mb = f"{peak/1e6:.0f}" if peak else "?"
        rows.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {rl['compute_s']*1e3:.2f} | {rl['memory_s']*1e3:.2f} "
            f"| {rl['collective_s']*1e3:.2f} | {rl['bottleneck']} "
            f"| {rl['useful_flop_ratio']:.3f} | {peak_mb} |"
        )
    return "\n".join(rows)


def run(report):
    # measured paged flash-decode budget (always available — no dry-run
    # artifacts needed); the pass/fail gate itself lives in bench_kernels
    from benchmarks.bench_kernels import paged_decode_rows

    for r in paged_decode_rows():
        report(
            f"roofline/paged_decode/B{r['B']}_d{r['depth']}"
            f"_bs{r['block_size']}", r["us"],
            f"path={r['path']} gather_us={r['gather_us']:.0f} "
            f"roofline_frac={r['roofline_frac']:.3f} "
            f"achieved_gbps={r['achieved_gbps']:.1f}",
        )

    results = load_results()
    if not results:
        report("roofline/missing", None,
               "run `python -m repro.launch.dryrun_all` first")
        return
    ok = [r for r in results if r.get("status") == "ok"]
    skipped = [r for r in results if r.get("status") == "skipped"]
    bad = [r for r in results if r.get("status") not in ("ok", "skipped")]
    report("roofline/combos", None,
           f"ok={len(ok)} skipped={len(skipped)} errors={len(bad)}")
    for r in ok:
        rl = r["roofline"]
        report(
            f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}", None,
            f"compute={rl['compute_s']*1e3:.2f}ms "
            f"memory={rl['memory_s']*1e3:.2f}ms "
            f"collective={rl['collective_s']*1e3:.2f}ms "
            f"bound={rl['bottleneck']} useful={rl['useful_flop_ratio']:.3f}",
        )
    # worst offenders (the hillclimb shortlist)
    def frac(r):
        rl = r["roofline"]
        dom = max(rl["compute_s"], rl["memory_s"], rl["collective_s"])
        return rl["compute_s"] / dom if dom else 0.0

    pod = [r for r in ok if r["mesh"] == "pod"]
    if pod:
        worst = min(pod, key=frac)
        coll = max(pod, key=lambda r: r["roofline"]["collective_s"])
        report("roofline/worst_compute_fraction", None,
               f"{worst['arch']}×{worst['shape']} frac={frac(worst):.3f}")
        report("roofline/most_collective_bound", None,
               f"{coll['arch']}×{coll['shape']} "
               f"coll={coll['roofline']['collective_s']*1e3:.1f}ms")


if __name__ == "__main__":
    enable_compile_cache()
    print(markdown_table(load_results()))
    print()
    print("## Paged flash-decode budget (measured)")
    print()
    print(paged_decode_table())
