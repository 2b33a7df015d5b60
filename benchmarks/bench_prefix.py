"""Prefix-reuse benchmark: a Zipf-shared prompt trace served through the
chunked ``ContinuousEngine`` with the radix-trie prompt cache on vs. off.

    PYTHONPATH=src python -m benchmarks.bench_prefix [--requests 14]

The trace (``repro.data.synthetic.make_prefix_trace``) mirrors production
prefix sharing: a small pool of multi-chunk system-prompt-style prefixes
with Zipf popularity, per-request suffixes of mixed length (including
zero — exact-duplicate prompts, the full-hit case), Poisson arrivals.

Reported:

* aggregate TTFT (mean and p95) with the cache off vs. on, and the ratio;
* the cache's hit rate, shared-prefix tokens skipped, and resident bytes;
* the TTFT of a *fully cached* prompt (served alone on a warmed cache)
  against the wall time of a single uncached chunk-prefill step — a full
  hit admits with zero prefill chunks, so it must come in under one chunk.

PASS requires both: full-hit TTFT < one uncached chunk's prefill time, and
>= 2x aggregate mean-TTFT improvement on the Zipf trace.  ``run(report)``
feeds the same verdict to ``benchmarks.ci_smoke``.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import clone_requests, engine_stats, ttft_stats
from repro.common.compile_cache import enable_compile_cache
from repro.common.config import EvictionConfig
from repro.configs import get_smoke_config
from repro.data.synthetic import make_prefix_trace
from repro.models import transformer as tf
from repro.serving import ContinuousEngine, PrefixCache, Request

CHUNK = 64
# TTFT benchmark: one token per request (retire at admission) so the
# off/on comparison isolates the prefill path instead of mixing in decode
MAX_NEW = 1
BUDGET = 16
POLICY = "h2o"  # cumulative scoring: cheapest finalize, fused-mass prefill


def _requests(cfg, *, n_requests, seed):
    trace = make_prefix_trace(
        seed, n_requests, cfg.vocab_size, chunk=CHUNK, n_prefixes=3,
        prefix_chunks=(6,), suffix_lens=(0, 0, 33, 64), zipf_a=1.3,
        rate_hz=200.0)
    return [Request(uid=i, prompt=p, max_new_tokens=MAX_NEW, arrival_s=a)
            for i, (p, a) in enumerate(trace)]


_clone = clone_requests
_ttft = ttft_stats


def _engine(cfg, params, *, prefix_cache=None, max_len):
    return ContinuousEngine(
        params, cfg, policy=POLICY, evict=EvictionConfig(budget=BUDGET),
        num_slots=4, chunk=CHUNK, max_context=max_len,
        max_new_tokens=MAX_NEW, eos_id=-1, decode_chunk=2,
        prefix_cache=prefix_cache)




def _chunk_step_time(cfg, params, eng, reps=20):
    """Median wall time of one compiled, uncached chunk-prefill step."""
    fn = eng.chunk_cache.get("chunk", CHUNK, 1, POLICY)
    state = tf.init_chunk_state(cfg, POLICY, 1, eng._base_cap)
    rng = np.random.default_rng(0)
    blk = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, CHUNK))
                      .astype(np.int32))
    n = jnp.asarray(4 * CHUNK, jnp.int32)
    fn(params, state, blk, n)[1].block_until_ready()  # compile
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(params, state, blk, n)[1].block_until_ready()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def bench(n_requests=14, seed=0):
    cfg = get_smoke_config("smollm-135m")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _requests(cfg, n_requests=n_requests, seed=seed)
    max_len = max(len(r.prompt) for r in reqs)
    eng_off = _engine(cfg, params, max_len=max_len)
    cache = PrefixCache(chunk=CHUNK, max_bytes=256 << 20)
    eng_on = _engine(cfg, params, prefix_cache=cache, max_len=max_len)
    # warmup replays: compile every program.  The cache-on engine needs
    # two — the first populates the trie, the second takes the *hit* path
    # and compiles the chain-materialize programs the timed replay reuses
    eng_off.run(_clone(reqs))
    eng_on.run(_clone(reqs))
    eng_on.run(_clone(reqs))
    res = {"off": _ttft(eng_off.run(_clone(reqs)))}
    done_on = eng_on.run(_clone(reqs))
    es = engine_stats(eng_on)
    res["on"] = _ttft(done_on)
    res["on"].update(
        hit_rate=es["prefix"]["hit_rate"],
        cached_token_frac=es["prefix"]["cached_token_frac"],
        tokens_skipped=es["prefix_tokens_skipped"],
        cache_bytes=cache.stats()["bytes"],
        entries=cache.stats()["entries"],
    )
    # fully cached prompts admitted on a warm, idle engine: TTFT must be
    # below even a single uncached chunk's prefill step.  A same-run warm
    # request absorbs the per-``run()`` setup (live-cache allocation), and
    # spaced late arrivals each admit on an idle engine, so the median
    # measures steady-state admission, not engine init or one-shot jitter.
    rng = np.random.default_rng(seed + 1)
    fulls = [Request(uid=10_000 + i,
                     prompt=reqs[0].prompt[:6 * CHUNK].copy(),
                     max_new_tokens=MAX_NEW, arrival_s=0.4 + 0.2 * i)
             for i in range(3)]
    warm = Request(uid=9_999, prompt=rng.integers(
        0, cfg.vocab_size, CHUNK).astype(np.int32), max_new_tokens=MAX_NEW)
    done = {r.uid: r for r in eng_on.run([warm] + fulls)}
    assert all(done[f.uid].cached_prefix_tokens == len(f.prompt)
               for f in fulls), "warmed trace did not cover the prefix"
    res["full_hit_ttft_s"] = float(np.median(
        [done[f.uid].ttft_s for f in fulls]))
    res["chunk_step_s"] = _chunk_step_time(cfg, params, eng_off)
    res["ttft_speedup"] = (res["off"]["ttft_mean_ms"]
                           / max(res["on"]["ttft_mean_ms"], 1e-9))
    return res


def _verdict(res) -> tuple[bool, str]:
    under_chunk = res["full_hit_ttft_s"] < res["chunk_step_s"]
    speedup = res["ttft_speedup"] >= 2.0
    ok = under_chunk and speedup
    return ok, (
        f"{'PASS' if ok else 'FAIL'}: full-hit TTFT "
        f"{1e3 * res['full_hit_ttft_s']:.2f}ms vs one chunk "
        f"{1e3 * res['chunk_step_s']:.2f}ms "
        f"({'under' if under_chunk else 'NOT under'}); aggregate TTFT "
        f"{res['ttft_speedup']:.2f}x ({'>=' if speedup else 'BELOW'} 2x), "
        f"hit-rate {res['on']['hit_rate']:.2f}")


def run(report):
    """benchmarks.ci_smoke entry point."""
    res = bench()
    report("prefix/ttft_mean_off_ms", None,
           f"{res['off']['ttft_mean_ms']:.1f}")
    report("prefix/ttft_mean_on_ms", None,
           f"{res['on']['ttft_mean_ms']:.1f}")
    report("prefix/ttft_speedup", None, f"{res['ttft_speedup']:.2f}x")
    report("prefix/hit_rate", None, f"{res['on']['hit_rate']:.2f}")
    report("prefix/cached_token_frac", None,
           f"{res['on']['cached_token_frac']:.2f}")
    report("prefix/cache_bytes", None, f"{res['on']['cache_bytes']}")
    report("prefix/full_hit_ttft_ms", None,
           f"{1e3 * res['full_hit_ttft_s']:.2f}")
    report("prefix/chunk_step_ms", None, f"{1e3 * res['chunk_step_s']:.2f}")
    ok, verdict = _verdict(res)
    report("prefix/reuse_verdict", None, "pass" if ok else "fail")
    print(verdict)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=14)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    res = bench(args.requests, args.seed)
    print(f"{'engine':8s} {'ttft_ms':>9s} {'ttft_p95':>9s}")
    for name in ("off", "on"):
        m = res[name]
        print(f"{name:8s} {m['ttft_mean_ms']:9.1f} {m['ttft_p95_ms']:9.1f}")
    on = res["on"]
    print(f"hit-rate {on['hit_rate']:.2f}  cached-token-frac "
          f"{on['cached_token_frac']:.2f}  entries {on['entries']}  "
          f"bytes {on['cache_bytes']}")
    print(_verdict(res)[1])


if __name__ == "__main__":
    main()
