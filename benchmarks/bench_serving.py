"""Serving benchmark: chunked vs bucketed continuous batching (and the
lockstep baseline) on Poisson traces, including a long-tail trace.

    PYTHONPATH=src python -m benchmarks.bench_serving [--long-tail]
    PYTHONPATH=src python -m benchmarks.bench_serving --long-tail \
        --long-len 8192 --n-long 2

One trace, replayed FCFS through each engine:

* **lockstep** (deprecated ``ServingEngine``): a batch must share one
  prompt length and prefill+decode run to completion before the next batch.
* **bucketed** (deprecated ``BucketedEngine``): pad-to-bucket *monolithic*
  prefill feeding fixed decode slots — every live slot stalls for the whole
  prefill of an admitted prompt, and each (bucket, batch, padded) shape
  compiles its own program.
* **chunked** (``ContinuousEngine``): one compiled ``(1, chunk)`` prefill
  program with streaming eviction scores, interleaved with decode under a
  token-budget step.

The **long-tail trace** plants a few 8k–16k prompts amid short traffic —
the shape that breaks the bucket ladder: the long prompts compile fresh
power-of-two bucket programs and stall every live decode slot for whole
monolithic prefills.  Reported per engine: throughput, p95 TTFT, p95 TPOT,
max decode stall (worst gap between consecutive token emissions of any
request), the jit-compile count, and the peak device KV bytes the engine
reserves (``kv_bytes_peak`` — BENCH_*.json tracks the memory trajectory
across PRs; ``benchmarks/bench_paged.py`` is the bench that *varies* it).  The chunked engine must compile
strictly fewer programs and cut p95 TPOT / decode stall under the long
tail — the bench prints an explicit PASS/FAIL verdict line.

``bench_decode_evict`` (``--decode-evict``; always part of the CI
``run`` entry) replays a **long-generation** trace through the paged
pool with decode-time eviction off vs on at equal KV pool bytes: the
``serving/decode_evict_verdict`` row passes iff sweeps reclaim whole
blocks mid-generation and lift peak concurrency, with every generation
still completing at full length.
"""

from __future__ import annotations

import argparse
import time
import warnings

import jax
import numpy as np

from benchmarks.common import (clone_requests, decode_step_stats,
                               engine_stats, make_poisson_trace, ttft_stats)
from repro.common.compile_cache import enable_compile_cache
from repro.common.config import EvictionConfig
from repro.configs import get_smoke_config
from repro.core.lookahead import init_lookahead_params
from repro.models import transformer as tf
from repro.serving import (BucketedEngine, ChunkingConfig, ContinuousEngine,
                           DecodeEvictionConfig, KVBlockPool, ServingConfig,
                           ServingEngine)

# Heterogeneous short lengths (9 distinct values over 3 compile buckets).
PROMPT_LENS = (17, 24, 31, 41, 48, 60, 75, 90, 120)
BUCKETS = (32, 64, 128)
CHUNK = 64
MAX_NEW = 16
BUDGET = 16


def make_trace(n_requests: int, rate_hz: float, seed: int, vocab: int,
               *, long_tail: bool = False, long_len: int = 8192,
               n_long: int = 2):
    """Poisson arrivals, uniform mix over PROMPT_LENS; with ``long_tail``,
    ``n_long`` prompts of ``long_len`` tokens are planted mid-trace."""
    long_uids = set()
    if long_tail and n_long:
        assert n_long <= max(n_requests // 3, 1), \
            "long tail would dominate the trace; raise --requests"
        # consecutive mid-trace uids: guaranteed n_long *distinct* plants
        # (an index formula that rounds, e.g. linspace, can collide and
        # silently shrink the tail)
        start = n_requests // 3
        long_uids = set(range(start, start + n_long))
    return make_poisson_trace(n_requests, vocab, PROMPT_LENS, seed=seed,
                              max_new=MAX_NEW, rate_hz=rate_hz,
                              long_uids=long_uids, long_len=long_len)


_clone = clone_requests


def _metrics(reqs, wall, *, tracks_gaps: bool = True):
    toks = sum(len(r.out_tokens) for r in reqs)
    tpot = np.array([r.tpot_s for r in reqs if r.tpot_s > 0])
    gaps = np.array([r.max_gap_s for r in reqs])
    m = {
        "wall_s": wall,
        "tok_per_s": toks / wall,
        "tpot_mean_ms": 1e3 * tpot.mean() if len(tpot) else 0.0,
        "tpot_p95_ms": 1e3 * np.percentile(tpot, 95) if len(tpot) else 0.0,
        # nan (printed as n/a) when the engine has no per-chunk emission
        # timestamps — the lockstep engine decodes a batch in one blocking
        # call, so a 0.0 here would misread as "never stalls"
        "stall_max_ms": (1e3 * gaps.max() if len(gaps) and tracks_gaps
                         else float("nan")),
    }
    m.update(ttft_stats(reqs))
    return m


def run_lockstep(eng, reqs, *, max_batch=4):
    """FCFS trace replay under the lockstep contract: serve the queue head
    together with every *arrived* request of the same prompt length."""
    queue = sorted(reqs, key=lambda r: r.arrival_s)
    done = []
    t0 = time.perf_counter()
    while queue:
        now = time.perf_counter() - t0
        arrived = [r for r in queue if r.arrival_s <= now]
        if not arrived:
            time.sleep(max(queue[0].arrival_s - now, 0.0))
            continue
        head = arrived[0]
        batch = [r for r in arrived
                 if len(r.prompt) == len(head.prompt)][:max_batch]
        for r in batch:
            queue.remove(r)
        serve_start = time.perf_counter() - t0
        eng.serve(batch)
        serve_end = time.perf_counter() - t0
        for r in batch:
            # r.ttft_s is still serve-relative here: split decode time off
            # first, then rebase TTFT onto the trace clock (queue wait incl.)
            decode_s = serve_end - serve_start - r.ttft_s
            r.tpot_s = decode_s / max(len(r.out_tokens) - 1, 1)
            r.ttft_s = serve_start + r.ttft_s - r.arrival_s
        done += batch
    m = _metrics(done, time.perf_counter() - t0, tracks_gaps=False)
    m["kv_bytes_peak"] = eng.kv_device_bytes(max_batch)
    return m


def run_bucketed(eng, reqs):
    t0 = time.perf_counter()
    done = eng.run(reqs)
    wall = time.perf_counter() - t0
    m = _metrics(done, wall)
    m["compiles"] = (eng.prefill_cache.compile_count()
                     + len(eng._decode_fns))
    m["compile_cache"] = eng.prefill_cache.stats()
    m["kv_bytes_peak"] = eng.kv_device_bytes()
    m.update(decode_step_stats(eng))
    return m


def run_chunked(eng, reqs):
    t0 = time.perf_counter()
    done = eng.run(reqs)
    wall = time.perf_counter() - t0
    m = _metrics(done, wall)
    m["compiles"] = (eng.chunk_cache.compile_count()
                     + len(eng._decode_fns))
    m["compile_cache"] = eng.chunk_cache.stats()
    s = engine_stats(eng)
    m["engine_stats"] = s
    m["kv_bytes_peak"] = eng.kv_device_bytes()
    # the serving mesh (None = single-device): BENCH_*.json rows must say
    # which device topology produced their numbers
    m["mesh"] = s.get("mesh")
    m.update(decode_step_stats(eng))
    return m


def bench(n_requests=24, rate_hz=20.0, policy="lookaheadkv", slots=4,
          seed=0, warmup=True, long_tail=False, long_len=8192, n_long=2,
          lockstep=False):
    cfg = get_smoke_config("smollm-135m")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    lkv = init_lookahead_params(jax.random.PRNGKey(1), cfg, params["layers"])
    trace = make_trace(n_requests, rate_hz, seed, cfg.vocab_size,
                       long_tail=long_tail, long_len=long_len, n_long=n_long)
    kw = dict(policy=policy, evict=EvictionConfig(budget=BUDGET),
              lkv_params=lkv, max_new_tokens=MAX_NEW, eos_id=-1)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        bucket_eng = BucketedEngine(params, cfg, num_slots=slots,
                                    buckets=BUCKETS, **kw)
        lock_eng = ServingEngine(params, cfg, **kw) if lockstep else None
    chunk_eng = ContinuousEngine(
        params, cfg,
        ServingConfig(policy=policy, evict=EvictionConfig(budget=BUDGET),
                      chunking=ChunkingConfig(
                          chunk=CHUNK, max_context=max(PROMPT_LENS) + CHUNK),
                      num_slots=slots, max_new_tokens=MAX_NEW, eos_id=-1),
        lkv_params=lkv)
    bucket_eng.warmup(PROMPT_LENS, batch_sizes=(1, 2, slots))
    chunk_eng.warmup(PROMPT_LENS)
    if warmup:  # one untimed replay per engine compiles every program
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            run_bucketed(bucket_eng, _clone(trace))
            if lock_eng is not None:
                run_lockstep(lock_eng, _clone(trace))
        run_chunked(chunk_eng, _clone(trace))
    out = {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        out["bucketed"] = run_bucketed(bucket_eng, _clone(trace))
        if lock_eng is not None:
            out["lockstep"] = run_lockstep(lock_eng, _clone(trace))
    out["chunked"] = run_chunked(chunk_eng, _clone(trace))
    return out


def bench_decode_evict(n_requests=8, policy="lookaheadkv", seed=0, *,
                       max_new=48, interval=16, block_size=16,
                       pool_blocks=10, slots=4, warmup=True):
    """Long-generation trace on the paged pool, decode-time eviction off
    vs on, at **equal KV pool bytes** (identical pool geometry).

    Off, every admitted request must reserve ``budget + max_new + 1``
    rows of pool for its whole lifetime; on, a slot's footprint is
    bounded at ``budget + interval`` rows because periodic sweeps
    re-evict the grown cache and free the tail blocks mid-generation —
    so the same pool admits more concurrent requests.  Reported per
    config: throughput, peak concurrency, pool high water, and the
    blocks reclaimed by sweeps."""
    cfg = get_smoke_config("smollm-135m")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    lkv = init_lookahead_params(jax.random.PRNGKey(1), cfg, params["layers"])
    trace = make_poisson_trace(n_requests, cfg.vocab_size, PROMPT_LENS[:5],
                               seed=seed, max_new=max_new, rate_hz=100.0)

    def engine(enabled):
        pool = KVBlockPool(cfg, block_size=block_size,
                           num_blocks=pool_blocks)
        sc = ServingConfig(
            policy=policy, evict=EvictionConfig(budget=BUDGET),
            decode_evict=DecodeEvictionConfig(enabled=enabled,
                                              interval=interval),
            chunking=ChunkingConfig(chunk=CHUNK,
                                    max_context=max(PROMPT_LENS) + CHUNK),
            num_slots=slots, max_new_tokens=max_new, eos_id=-1,
            kv_pool=pool)
        return ContinuousEngine(params, cfg, sc, lkv_params=lkv), pool

    out = {}
    for name, enabled in (("paged", False), ("paged_evict", True)):
        eng, pool = engine(enabled)
        if warmup:
            eng.run(_clone(trace))
        t0 = time.perf_counter()
        done = eng.run(_clone(trace))
        wall = time.perf_counter() - t0
        es = engine_stats(eng)
        s = es["kv_pool"]
        out[name] = {
            "wall_s": wall,
            "tok_per_s": sum(len(r.out_tokens) for r in done) / wall,
            "full_length": all(len(r.out_tokens) == max_new for r in done),
            "max_concurrency": es["max_concurrency"],
            "pool_bytes": s["bytes_total"],
            "high_water_blocks": s["high_water_blocks"],
            "sweeps": es.get("decode_evict_sweeps", 0),
            "blocks_reclaimed": s["blocks_reclaimed_decode"],
            "preemptions": es["preemptions"],
        }
    return out


def _decode_evict_verdict(res) -> tuple[bool, str]:
    off, on = res["paged"], res["paged_evict"]
    assert off["pool_bytes"] == on["pool_bytes"], \
        "the comparison is only meaningful at equal KV pool bytes"
    more = on["max_concurrency"] > off["max_concurrency"]
    reclaims = on["blocks_reclaimed"] > 0
    complete = on["full_length"] and off["full_length"]
    ok = more and reclaims and complete
    return ok, (f"{'PASS' if ok else 'FAIL'}: at "
                f"{off['pool_bytes'] / 1e6:.2f} MB of pool, decode "
                f"eviction lifts peak concurrency "
                f"{off['max_concurrency']} -> {on['max_concurrency']} "
                f"({'more' if more else 'NOT more'}); "
                f"{on['sweeps']} sweeps reclaimed "
                f"{on['blocks_reclaimed']} blocks mid-generation "
                f"({'some' if reclaims else 'NONE'}); generations "
                f"{'complete' if complete else 'TRUNCATED'}")


def _verdict(res) -> tuple[bool, str]:
    b, c = res["bucketed"], res["chunked"]
    fewer = c["compiles"] < b["compiles"]
    faster = c["tpot_p95_ms"] < b["tpot_p95_ms"]
    ok = fewer and faster
    return ok, (f"{'PASS' if ok else 'FAIL'}: chunked compiles "
                f"{c['compiles']} vs bucketed {b['compiles']} "
                f"({'strictly fewer' if fewer else 'NOT fewer'}); "
                f"p95 TPOT {c['tpot_p95_ms']:.2f}ms vs "
                f"{b['tpot_p95_ms']:.2f}ms "
                f"({'lower' if faster else 'NOT lower'})")


def run(report):
    """benchmarks.run entry point: a compact long-tail trace."""
    res = bench(n_requests=12, rate_hz=20.0, long_tail=True, long_len=2048,
                n_long=1, warmup=True)
    for name in ("bucketed", "chunked"):
        m = res[name]
        report(f"serving/{name}_tok_per_s", None, f"{m['tok_per_s']:.1f}")
        report(f"serving/{name}_ttft_p95_ms", None,
               f"{m['ttft_p95_ms']:.0f}")
        report(f"serving/{name}_tpot_p95_ms", None,
               f"{m['tpot_p95_ms']:.2f}")
        report(f"serving/{name}_stall_max_ms", None,
               f"{m['stall_max_ms']:.0f}")
        report(f"serving/{name}_compiles", None, f"{m['compiles']}")
        report(f"serving/{name}_decode_step_ms", None,
               f"{m['decode_step_ms']:.2f} path={m['decode_path']}")
        # peak device KV bytes per config: BENCH_*.json tracks the memory
        # trajectory across PRs, not just latency/throughput
        report(f"serving/{name}_kv_bytes_peak", None,
               f"{m['kv_bytes_peak']}")
    report("serving/chunked_mesh", None,
           str(res["chunked"]["mesh"] or "single-device"))
    ok, verdict = _verdict(res)
    report("serving/longtail_verdict", None, "pass" if ok else "fail")
    speed = (res["chunked"]["tok_per_s"]
             / max(res["bucketed"]["tok_per_s"], 1e-9))
    report("serving/chunked_speedup", None, f"{speed:.2f}x")
    # decode-time eviction on the paged pool: concurrency at equal KV bytes
    de = bench_decode_evict(n_requests=6, warmup=True)
    for name in ("paged", "paged_evict"):
        m = de[name]
        report(f"serving/{name}_tok_per_s", None, f"{m['tok_per_s']:.1f}")
        report(f"serving/{name}_max_concurrency", None,
               str(m["max_concurrency"]))
    report("serving/decode_evict_sweeps", None,
           str(de["paged_evict"]["sweeps"]))
    report("serving/decode_evict_blocks_reclaimed", None,
           str(de["paged_evict"]["blocks_reclaimed"]))
    ok_de, _ = _decode_evict_verdict(de)
    report("serving/decode_evict_verdict", None, "pass" if ok_de else "fail")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--policy", default="lookaheadkv")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-warmup", action="store_true")
    ap.add_argument("--long-tail", action="store_true",
                    help="plant a few long prompts amid short traffic")
    ap.add_argument("--long-len", type=int, default=8192,
                    help="long-tail prompt length (8k-16k is the target)")
    ap.add_argument("--n-long", type=int, default=2)
    ap.add_argument("--lockstep", action="store_true",
                    help="also replay through the lockstep baseline")
    ap.add_argument("--decode-evict", action="store_true",
                    help="also run the paged-pool decode-eviction "
                         "comparison (concurrency at equal KV bytes)")
    args = ap.parse_args()
    enable_compile_cache()
    res = bench(args.requests, args.rate, args.policy, args.slots,
                args.seed, warmup=not args.no_warmup,
                long_tail=args.long_tail, long_len=args.long_len,
                n_long=args.n_long, lockstep=args.lockstep)
    print(f"{'engine':10s} {'tok/s':>8s} {'ttft_ms':>9s} {'ttft_p95':>9s} "
          f"{'tpot_ms':>8s} {'tpot_p95':>9s} {'stall_ms':>9s} "
          f"{'compiles':>8s} {'wall_s':>7s} {'step_ms':>8s} {'path':>9s}")
    for name, m in res.items():
        stall = (f"{m['stall_max_ms']:9.1f}"
                 if np.isfinite(m["stall_max_ms"]) else f"{'n/a':>9s}")
        step = (f"{m['decode_step_ms']:8.2f}"
                if "decode_step_ms" in m else f"{'n/a':>8s}")
        print(f"{name:10s} {m['tok_per_s']:8.1f} {m['ttft_mean_ms']:9.1f} "
              f"{m['ttft_p95_ms']:9.1f} {m['tpot_mean_ms']:8.2f} "
              f"{m['tpot_p95_ms']:9.2f} {stall} "
              f"{m.get('compiles', 0):8d} {m['wall_s']:7.2f} "
              f"{step} {m.get('decode_path', 'n/a'):>9s}")
    ratio = (res["chunked"]["tok_per_s"]
             / max(res["bucketed"]["tok_per_s"], 1e-9))
    print(f"chunked/bucketed throughput: {ratio:.2f}x  "
          f"(chunked cache: {res['chunked']['compile_cache']}, "
          f"engine: {res['chunked']['engine_stats']})")
    if args.long_tail:
        print(_verdict(res)[1])
    if args.decode_evict:
        de = bench_decode_evict(args.requests, args.policy, args.seed,
                                warmup=not args.no_warmup)
        for name, m in de.items():
            print(f"{name:12s} {m['tok_per_s']:8.1f} tok/s  concurrency "
                  f"{m['max_concurrency']}  high water "
                  f"{m['high_water_blocks']} blocks  {m['sweeps']} sweeps  "
                  f"{m['blocks_reclaimed']} reclaimed  "
                  f"{m['preemptions']} preemptions")
        print(_decode_evict_verdict(de)[1])


if __name__ == "__main__":
    main()
