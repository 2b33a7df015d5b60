"""Production serving launcher: prefill+evict+decode under a mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
        --policy lookaheadkv --budget 16 --requests 4

Loads lookahead modules from --lkv-ckpt when given (else random init — fine
for plumbing checks; quality requires training, see launch/train.py).
"""

from __future__ import annotations

import argparse
import time
import warnings

import jax
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.common.sharding import init_params_sharded
from repro.common.config import EvictionConfig
from repro.configs import get_config, get_smoke_config
from repro.core import policies
from repro.core.lookahead import (init_lookahead_params,
                                  load_lookahead_params)
from repro.models import transformer as tf
from repro.obs import TraceRecorder, phase_table
from repro.serving import (BucketedEngine, ChunkingConfig, ContinuousEngine,
                           DecodeEvictionConfig, KVBlockPool, PrefixCache,
                           Request, ServingConfig, ServingEngine)


def _print_phase_table(trace, done) -> None:
    """Per-request phase-latency breakdown from the span trace — where
    each request's TTFT actually went, instead of a flat stats dump."""
    rows = phase_table(trace, [r.uid for r in done])
    print(f"{'uid':>4s} {'pfx_skip':>8s} {'prefill_ms':>10s} "
          f"{'first_tok_ms':>12s} {'decode_ms':>9s} {'sweeps':>6s} "
          f"{'sweep_ms':>8s} {'replays':>7s} {'outcome':>9s}")
    for row in rows:
        if row["outcome"] == "missing":
            print(f"{row['uid']:4d} {'never admitted':>14s}")
            continue
        ft = (f"{row['first_token_ms']:12.1f}"
              if row["first_token_ms"] is not None else f"{'n/a':>12s}")
        print(f"{row['uid']:4d} {row['prefix_skip_tokens']:8d} "
              f"{row['prefill_ms']:10.1f} {ft} {row['decode_ms']:9.1f} "
              f"{row['sweeps']:6d} {row['sweep_ms']:8.1f} "
              f"{row['replays']:7d} {row['outcome']:>9s}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--policy", default="lookaheadkv")
    ap.add_argument("--budget", type=int, default=16)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--n-in", type=int, default=96)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--lkv-ckpt", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="serve mixed-length traffic through the "
                         "continuous-batching engine")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=32,
                    help="prefill chunk size (continuous engine)")
    ap.add_argument("--prefix-cache-mb", type=int, default=0,
                    help="radix-trie prompt-cache budget in MB (continuous "
                         "engine; 0 disables prefix reuse)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="tokens of a shared system-prompt prefix planted "
                         "in every request (rounded down to whole chunks)")
    ap.add_argument("--kv-pool-mb", type=float, default=0,
                    help="paged KV memory: decode caches live in a shared "
                         "block pool of this many MB, admission is gated "
                         "by free blocks, and eviction frees real device "
                         "memory (continuous engine; 0 = dense slot "
                         "caches, the old behavior)")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="rows per KV pool block (with --kv-pool-mb)")
    ap.add_argument("--decode-evict", action="store_true",
                    help="decoding-stage eviction: with --kv-pool-mb the "
                         "cache grows block-by-block and periodic sweeps "
                         "re-evict it to the budget, freeing blocks "
                         "mid-generation; dense engines cap the cache at a "
                         "small fixed margin instead")
    ap.add_argument("--decode-evict-interval", type=int, default=64,
                    help="rows of decode growth between eviction sweeps "
                         "(paged pool; bounds a slot's footprint at "
                         "capacity + interval rows)")
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="tensor-parallel shards: serve one sharded model "
                         "over a (data, model) device mesh (continuous "
                         "engine; 1 = single-device, the old behavior)")
    ap.add_argument("--metrics-json", default="",
                    help="write the engine's typed-metrics registry as a "
                         "JSON snapshot to this path after the run "
                         "(chunked continuous engine)")
    ap.add_argument("--prom-snapshot", default="",
                    help="write the registry in Prometheus text exposition "
                         "format to this path after the run")
    ap.add_argument("--trace-out", default="",
                    help="write the per-request span trace here: a .jsonl "
                         "path gets raw events, anything else Chrome "
                         "trace-event JSON (open in https://ui.perfetto.dev)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    streamable = (args.continuous and args.policy not in policies.MULTI_PASS
                  and args.policy != "full")
    mesh = None
    if args.mesh_model > 1:
        a = cfg.attn
        if not streamable:
            ap.error("--mesh-model requires the chunked continuous engine "
                     "(--continuous with a streamable policy)")
        if (a is None or a.num_kv_heads % args.mesh_model
                or a.num_heads % args.mesh_model):
            heads = None if a is None else (a.num_heads, a.num_kv_heads)
            ap.error(f"--mesh-model {args.mesh_model} does not divide "
                     f"{args.arch}'s (q, kv) heads {heads}")
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model=args.mesh_model)
        print(f"mesh: {dict(mesh.shape)} over {len(jax.devices())} devices")
    key = jax.random.PRNGKey(args.seed)
    if mesh is not None:
        params = init_params_sharded(key, cfg, mesh)
    else:
        params = tf.init_params(key, cfg)
    lkv = None
    if cfg.technique_applies and cfg.lookahead:
        lkv = init_lookahead_params(jax.random.PRNGKey(args.seed + 1), cfg,
                                    params["layers"])
        if args.lkv_ckpt:
            lkv = load_lookahead_params(args.lkv_ckpt, cfg, params["layers"])
            print(f"loaded lookahead modules from {args.lkv_ckpt}")

    rng = np.random.default_rng(args.seed)
    if args.prefix_cache_mb and not streamable:
        print("note: --prefix-cache-mb requires the chunked continuous "
              "engine (--continuous with a streamable policy); ignoring it")
    if args.shared_prefix and not args.continuous:
        print("note: --shared-prefix shapes --continuous traffic only; "
              "ignoring it")
    if args.kv_pool_mb and not streamable:
        print("note: --kv-pool-mb requires the chunked continuous engine "
              "(--continuous with a streamable policy); ignoring it")
        args.kv_pool_mb = 0
    trace = None  # set on the chunked continuous path
    if args.continuous:
        if args.policy in policies.MULTI_PASS or args.policy == "full":
            # draft-based baselines and 'full' cannot stream prefill chunks;
            # fall back to the deprecated bucketed engine for them
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", DeprecationWarning)
                eng = BucketedEngine(
                    params, cfg, policy=args.policy,
                    evict=EvictionConfig(budget=args.budget, draft_len=8),
                    lkv_params=lkv, num_slots=args.slots,
                    max_new_tokens=args.max_new, eos_id=-1)
        else:
            kv_pool = None
            if args.kv_pool_mb:
                kv_pool = KVBlockPool(cfg, block_size=args.kv_block_size,
                                      pool_mb=args.kv_pool_mb, mesh=mesh)
            prefix_cache = None
            if args.prefix_cache_mb:
                # with a pool, cached prefixes pin pool blocks (one
                # physical copy shared with decode) instead of owning a
                # second device-resident copy
                prefix_cache = PrefixCache(
                    chunk=args.chunk,
                    max_bytes=args.prefix_cache_mb << 20, pool=kv_pool)
            decode_evict = args.decode_evict
            if decode_evict and kv_pool is not None and mesh is not None:
                print("note: decode-time eviction on the paged pool is "
                      "single-device; ignoring --decode-evict under "
                      "--mesh-model")
                decode_evict = False
            # span tracing is always on for the chunked engine: it is the
            # per-request latency attribution this launcher reports, and
            # the obs bench gates its overhead at < 3% of throughput
            trace = TraceRecorder()
            sc = ServingConfig(
                policy=args.policy,
                evict=EvictionConfig(budget=args.budget, draft_len=8),
                decode_evict=DecodeEvictionConfig(
                    enabled=decode_evict,
                    interval=args.decode_evict_interval),
                chunking=ChunkingConfig(
                    chunk=args.chunk,
                    max_context=max(args.n_in, args.chunk)),
                num_slots=args.slots, max_new_tokens=args.max_new,
                eos_id=-1, prefix_cache=prefix_cache, kv_pool=kv_pool,
                mesh=mesh, trace=trace)
            eng = ContinuousEngine(params, cfg, sc, lkv_params=lkv)
        shared = (args.shared_prefix // args.chunk) * args.chunk
        system = rng.integers(0, cfg.vocab_size, shared).astype(np.int32)
        lens = rng.integers(args.n_in // 2, args.n_in + 1, args.requests)
        reqs = [Request(uid=i,
                        prompt=np.concatenate([
                            system,
                            rng.integers(0, cfg.vocab_size,
                                         int(n)).astype(np.int32)]),
                        max_new_tokens=args.max_new)
                for i, n in enumerate(lens)]
        t0 = time.time()
        done = eng.run(reqs)
        wall = time.time() - t0
        if trace is not None:
            # where each request's latency went, phase by phase — the
            # span trace replaces the old flat stats dump
            _print_phase_table(trace, done)
        if getattr(eng, "prefix_cache", None) is not None:
            m = eng.metrics
            hits = int(m.value("serving_prefix_hits_total"))
            misses = int(m.value("serving_prefix_misses_total"))
            skipped = int(m.value("serving_prefix_tokens_skipped_total"))
            prompt_tokens = sum(len(r.prompt) for r in done)
            print(f"prefix cache: hit-rate "
                  f"{hits / max(hits + misses, 1):.2f}, "
                  f"{skipped}/{prompt_tokens} prompt tokens "
                  f"served from shared prefixes, "
                  f"{eng.prefix_cache.stats()['bytes'] / 1e6:.2f} MB resident")
        if getattr(eng, "pool", None) is not None:
            m = eng.metrics
            s = eng.pool.stats()
            print(f"kv pool: {s['blocks_total']} x {s['block_size']}-row "
                  f"blocks ({s['bytes_total'] / 1e6:.2f} MB), high water "
                  f"{s['high_water_blocks']} blocks, peak concurrency "
                  f"{int(m.value('serving_max_concurrency'))}, "
                  f"{int(m.value('serving_preemptions_total'))} preemptions, "
                  f"{s['blocks_pinned_prefix']} blocks pinned by the "
                  f"prefix cache")
            if sc.decode_evict.enabled:
                print(f"decode eviction: "
                      f"{int(m.value('serving_decode_evict_sweeps_total'))} "
                      f"sweeps reclaimed {s['blocks_reclaimed_decode']} "
                      f"blocks mid-generation")
    else:
        with warnings.catch_warnings():  # explicit lockstep-baseline request
            warnings.simplefilter("ignore", DeprecationWarning)
            eng = ServingEngine(
                params, cfg, policy=args.policy,
                evict=EvictionConfig(budget=args.budget, draft_len=8),
                lkv_params=lkv, max_new_tokens=args.max_new, eos_id=-1)
        reqs = [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            args.n_in).astype(np.int32),
                        max_new_tokens=args.max_new)
                for i in range(args.requests)]
        t0 = time.time()
        done = eng.serve(reqs)
        wall = time.time() - t0
    cb = eng.cache_bytes(args.n_in)
    print(f"policy={args.policy} budget={args.budget} "
          f"requests={len(done)} ttft={done[0].ttft_s*1e3:.1f}ms "
          f"wall={wall:.2f}s cache_ratio={cb['ratio']:.1f}x "
          f"({cb['full']/1e3:.0f}KB -> {cb['evicted']/1e3:.0f}KB per req)")
    for r in done[:2]:
        print(f"  req {r.uid}: {len(r.out_tokens)} tokens "
              f"{r.out_tokens[:8]}...")
    # observability artifacts (chunked continuous engine only: the
    # deprecated engines predate the registry/tracer)
    metrics = getattr(eng, "metrics", None)
    if args.metrics_json:
        if metrics is None:
            print("note: --metrics-json needs the chunked continuous "
                  "engine; skipped")
        else:
            metrics.to_json(args.metrics_json)
            print(f"metrics snapshot -> {args.metrics_json}")
    if args.prom_snapshot:
        if metrics is None:
            print("note: --prom-snapshot needs the chunked continuous "
                  "engine; skipped")
        else:
            with open(args.prom_snapshot, "w") as f:
                f.write(metrics.prometheus_text())
            print(f"prometheus snapshot -> {args.prom_snapshot}")
    if args.trace_out:
        if trace is None:
            print("note: --trace-out needs the chunked continuous "
                  "engine; skipped")
        elif args.trace_out.endswith(".jsonl"):
            trace.to_jsonl(args.trace_out)
            print(f"span trace (jsonl) -> {args.trace_out}")
        else:
            trace.to_chrome(args.trace_out)
            print(f"span trace (perfetto) -> {args.trace_out}")


if __name__ == "__main__":
    main()
