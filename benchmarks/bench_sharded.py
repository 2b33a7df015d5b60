"""Sharded-serving benchmark: tensor-parallel paged decode over a forced
host device mesh, at model = {1, 2, 4}.

    PYTHONPATH=src python -m benchmarks.bench_sharded

The measurement child re-execs under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the parent's JAX
is typically already initialized single-device, and the flag only takes
effect before the backend loads), serves one Poisson trace through the
paged ``ContinuousEngine`` at each model-axis width, and reports:

* decode throughput (tok/s) and per-device decode throughput (tok/s
  divided by the mesh's device count — on a *forced host* mesh every
  "device" timeshares one CPU, so wall throughput is flat-to-worse as
  model grows; the per-device number is the figure that transfers to a
  real accelerator mesh);
* KV pool bytes per shard — the number tensor parallelism actually
  scales: each shard holds only its kv-head slice of every block.

``sharded/scaling_verdict`` (gated in ``benchmarks.ci_smoke``) passes iff

* per-shard pool bytes scale exactly as total/model at model = 2 and 4
  (the pool's kv-head dim is sharded, block tables replicated),
* every config emits bit-identical tokens (same uid -> same sequence) —
  the tentpole bit-exactness contract, re-checked here end-to-end on the
  bench trace (``tests/test_sharded_serving.py`` is the adversarial
  version with kept-set equality), and
* sharded wall throughput stays above ``TPUT_FLOOR`` x the single-device
  run.  The bound is deliberately loose (0.1x): 8 forced host "devices"
  timeshare one CPU, so sharding *cannot* speed this host up — the gate
  only catches pathological shard_map overhead (e.g. a per-step
  recompile), while real scaling is the per-device column on an
  accelerator mesh.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from repro.common.compile_cache import enable_compile_cache

MODEL_WIDTHS = (1, 2, 4)
N_DEVICES = 8
CHUNK = 32
MAX_NEW = 8
N_REQUESTS = 6
TPUT_FLOOR = 0.1  # see module docstring: a pathology guard, not a target
_MARK = "BENCH_SHARDED_JSON:"


def _child_bench() -> dict:
    """Runs inside the forced-8-device subprocess."""
    import dataclasses

    import jax
    import numpy as np

    from benchmarks.common import make_poisson_trace
    from repro.common.config import EvictionConfig
    from repro.configs import get_smoke_config
    from repro.core.lookahead import init_lookahead_params
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as tf
    from repro.serving import ContinuousEngine, KVBlockPool

    base = get_smoke_config("smollm-135m")
    # smollm's single kv head can't shard: widen to 8 q / 4 kv heads (the
    # same geometry tests/test_sharded_serving.py proves bit-exact)
    cfg = dataclasses.replace(
        base, name="smollm-smoke-tp", d_model=128,
        attn=dataclasses.replace(base.attn, num_heads=8, num_kv_heads=4,
                                 head_dim=16))
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    lkv = init_lookahead_params(jax.random.PRNGKey(1), cfg,
                                params["layers"])
    out: dict = {"devices": len(jax.devices()), "configs": {}}
    for model in MODEL_WIDTHS:
        mesh = make_host_mesh(model=model) if model > 1 else None
        pool = KVBlockPool(cfg, block_size=16, num_blocks=128, mesh=mesh)
        eng = ContinuousEngine(
            params, cfg, policy="lookaheadkv",
            evict=EvictionConfig(budget=16), lkv_params=lkv, num_slots=3,
            chunk=CHUNK, max_context=2 * CHUNK, max_new_tokens=MAX_NEW,
            eos_id=-1, kv_pool=pool, mesh=mesh)
        # near-burst arrivals: admission order must be identical across
        # widths or token comparison measures scheduler timing, not math
        trace = make_poisson_trace(
            N_REQUESTS, cfg.vocab_size, (17, 24, 31, 48), seed=0,
            max_new=MAX_NEW, gap_s=1e-6)
        eng.run([r.clone() for r in trace])  # compile off the clock
        done = eng.run([r.clone() for r in trace])
        from benchmarks.common import engine_stats
        es = engine_stats(eng)
        toks = sum(len(r.out_tokens) for r in done)
        steps = max(es.get("decode_steps", 0), 1)
        decode_s = max(es.get("decode_time_s", 0.0), 1e-9)
        s = es["kv_pool"]
        out["configs"][str(model)] = {
            "tok_per_s": toks / decode_s,
            "decode_step_ms": 1e3 * decode_s / steps,
            "bytes_total": s["bytes_total"],
            "bytes_per_shard": s.get("bytes_total_per_shard",
                                     s["bytes_total"]),
            "mesh": es.get("mesh"),
            "tokens": {int(r.uid): [int(t) for t in r.out_tokens]
                       for r in done},
        }
    return out


def bench() -> dict:
    """Spawn the forced-multi-device child and collect its measurements.

    The child measures a forced *host* mesh, so it is pinned to the CPU
    backend: a parent that already holds an accelerator (ci_smoke's earlier
    suites) would otherwise leave the child waiting on the device."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={N_DEVICES}"
    ).strip()
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_sharded", "--child"],
        capture_output=True, text=True, env=env, timeout=900)
    for line in proc.stdout.splitlines():
        if line.startswith(_MARK):
            return json.loads(line[len(_MARK):])
    raise RuntimeError(
        f"sharded bench child failed (rc={proc.returncode}):\n"
        f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")


def _verdict(res) -> tuple[bool, str]:
    cfgs = res["configs"]
    base = cfgs["1"]
    kv_ok = all(
        cfgs[str(m)]["bytes_per_shard"] == base["bytes_total"] // m
        for m in MODEL_WIDTHS if m > 1)
    tok_ok = all(cfgs[str(m)]["tokens"] == base["tokens"]
                 for m in MODEL_WIDTHS if m > 1)
    tput_ok = all(
        cfgs[str(m)]["tok_per_s"] >= TPUT_FLOOR * base["tok_per_s"]
        for m in MODEL_WIDTHS if m > 1)
    ok = kv_ok and tok_ok and tput_ok
    shards = " ".join(
        f"model={m}:{cfgs[str(m)]['bytes_per_shard']}B/shard"
        for m in MODEL_WIDTHS)
    return ok, (
        f"{'PASS' if ok else 'FAIL'}: per-shard KV bytes "
        f"{'scale as total/model' if kv_ok else 'do NOT scale'} "
        f"({shards}); tokens "
        f"{'bit-identical' if tok_ok else 'DIVERGE'} across widths; "
        f"sharded throughput {'within' if tput_ok else 'BELOW'} the "
        f"{TPUT_FLOOR}x host-mesh floor")


def run(report):
    """benchmarks.ci_smoke entry point."""
    from benchmarks.common import report_rows

    res = bench()
    for m in MODEL_WIDTHS:
        c = res["configs"][str(m)]
        devices = res["devices"]
        report_rows(report, "sharded", {
            f"model{m}_tok_per_s": f"{c['tok_per_s']:.1f}",
            f"model{m}_tok_per_s_per_device":
                f"{c['tok_per_s'] / devices:.1f}",
            f"model{m}_decode_step_ms": f"{c['decode_step_ms']:.2f}",
            f"model{m}_kv_bytes_per_shard": f"{c['bytes_per_shard']}",
            f"model{m}_mesh": str(c["mesh"] or "single-device"),
        })
    ok, verdict = _verdict(res)
    report("sharded/scaling_verdict", None, "pass" if ok else "fail")
    print(verdict)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true",
                    help="internal: run the measurement in-process")
    args = ap.parse_args()
    enable_compile_cache()
    if args.child:
        print(_MARK + json.dumps(_child_bench()), flush=True)
        return
    res = bench()
    devices = res["devices"]
    print(f"{'model':>5s} {'tok/s':>8s} {'tok/s/dev':>10s} "
          f"{'step_ms':>8s} {'B/shard':>10s} {'mesh':>24s}")
    for m in MODEL_WIDTHS:
        c = res["configs"][str(m)]
        print(f"{m:5d} {c['tok_per_s']:8.1f} "
              f"{c['tok_per_s'] / devices:10.1f} "
              f"{c['decode_step_ms']:8.2f} {c['bytes_per_shard']:10d} "
              f"{str(c['mesh'] or 'single-device'):>24s}")
    print(_verdict(res)[1])


if __name__ == "__main__":
    main()
