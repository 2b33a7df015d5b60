"""Bring-up smoke test: serve a full-width model on a TPU through the normal
serving entry points, and check what comes out.

    python chip_smoke.py [--seed 0]          # qwen2-1.5b on one chip
    python chip_smoke.py --four-chips        # llama3-8b tensor-parallel, 4 chips

One chip (default): qwen2-1.5b at its published widths (28 layers, d_model
1536, 12 heads over 2 kv heads of 128, vocab 151936) with bf16 weights and
random lookahead modules, all made from ``--seed``, served by
``ContinuousEngine`` the way ``repro.launch.serve --continuous
--kv-pool-mb`` builds it: policy ``lookaheadkv``, chunked prefill, a paged
``KVBlockPool`` and decode-time eviction.  Checks: every request finishes
at its length; every request's first-token logits are finite and their
argmax is the served token; the engine reports the fused Pallas scoring
path and the paged decode kernel; and for the longest request the logits
and kept (layer, head, position) sets are compared with a plain-jnp run of
the same prefill under ``ops.reference_mode()`` on the same chip.

Four chips (``--four-chips``): llama3-8b (32 layers, too large for one
chip's 16 GB) served tensor-parallel at ``model=4``, its params created
already sharded; then the same widths cut to 4 layers served on one chip
and at ``model=4``, whose tokens and kept sets must be identical.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any failed
check exits non-zero before it is printed; so does a machine whose JAX
finds no TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# The compared logits come from two paths that both carry bf16 activations
# (8 significant bits, a relative step of 2^-8) through every layer, but
# round them at different points: the Pallas kernels accumulate attention
# in f32 tiles, the jnp reference in XLA's own order.  Each layer may move
# a logit by a few bf16 steps of its scale, so the bound is 2^-4 (16
# bf16 steps) of the largest reference logit, and never below 2^-4.
LOGIT_RTOL = 2.0 ** -4

# one-chip traffic: prompts of 1k-4k tokens and 32-160 new tokens; with a
# decode-eviction sweep every SWEEP_INTERVAL rows of growth, the longer
# generations are re-evicted mid-decode
PROMPT_LENS = (1024, 4000, 2311, 3072, 1500, 2900)
MAX_NEW = (32, 160, 64, 96, 48, 128)
SWEEP_INTERVAL = 64


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def peak_hbm_gib(devices) -> list:
    return [round(d.memory_stats()["peak_bytes_in_use"] / 2**30, 3)
            for d in devices]


def make_requests(cfg, rng, lengths, max_new):
    from repro.serving import Request
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=m)
            for i, (n, m) in enumerate(zip(lengths, max_new))]


def clones(reqs):
    return [r.clone() for r in reqs]


def kept_sets(cache) -> dict:
    """{(layer, kv head): frozenset of kept positions} of one request's
    cache leaves mask/pos: (L, 1, C, KV) at admission, (L, C, KV) at
    retirement."""
    mask, pos = np.asarray(cache["mask"]), np.asarray(cache["pos"])
    if mask.ndim == 4:
        mask, pos = mask[:, 0], pos[:, 0]
    return {(layer, h): frozenset(pos[layer, mask[layer, :, h], h].tolist())
            for layer in range(mask.shape[0]) for h in range(mask.shape[2])}


def serve(eng, reqs, label):
    t = time.perf_counter()
    done = eng.run(clones(reqs))
    secs = time.perf_counter() - t
    done = sorted(done, key=lambda r: r.uid)
    check(len(done) == len(reqs), f"{label}: {len(done)}/{len(reqs)} served")
    for r, want in zip(done, reqs):
        check(r.done and len(r.out_tokens) == want.max_new_tokens,
              f"{label}: request {r.uid} stopped at {len(r.out_tokens)} "
              f"of {want.max_new_tokens} tokens")
        check(all(0 <= t < eng.cfg.vocab_size for t in r.out_tokens),
              f"{label}: request {r.uid} emitted an out-of-vocab token")
    return done, secs


def check_build(eng, label):
    build = eng.metrics.value("serving_build")
    log(f"{label}: serving_build score_path={build['score_path']} "
        f"decode_path={build['decode_path']} mesh={build['mesh']}")
    check(build["score_path"] == "pallas-fused",
          f"{label}: eviction scores took the {build['score_path']} tier")
    check(build["decode_path"] == "kernel",
          f"{label}: paged decode took the {build['decode_path']} tier")


def prefill_programs(eng, *, reference: bool):
    """(chunk, finalize) programs of the engine's prefill.  Served: the
    engine's own compiled programs.  Reference: the same bodies under a
    fresh ``jax.jit``, to be traced inside ``ops.reference_mode()`` (plain
    jnp on every op; the mode is read at trace time)."""
    import jax

    if reference:
        return (jax.jit(eng._build("chunk", eng.policy)),
                jax.jit(eng._build("finalize", eng.policy)))
    return (eng.chunk_cache.get("chunk", eng.chunk, 1, eng.policy),
            eng.chunk_cache.get("finalize", eng.chunk, 1, eng.policy))


def direct_prefill(eng, req, programs):
    """One request's chunked prefill + finalize, run by hand so its
    first-token logits can be read: (logits (V,) f32, attention cache)."""
    import jax.numpy as jnp

    from repro.models import transformer as tf

    chunk_fn, fin_fn = programs
    C, n = eng.chunk, len(req.prompt)
    padded = np.zeros(-(-n // C) * C, np.int32)
    padded[:n] = req.prompt
    nt = jnp.asarray(n, jnp.int32)
    # the KV-buffer depth the engine used for this prompt
    state = tf.init_chunk_state(eng.cfg, eng.policy, 1,
                                eng._request_context(n))
    for s in range(0, n, C):
        state, logits = chunk_fn(eng.params, state,
                                 jnp.asarray(padded[None, s:s + C]), nt)
    cache = fin_fn(eng.params, eng.lkv_params, state, nt,
                   jnp.asarray([req.eviction_seed], jnp.int32))
    return np.asarray(logits[0], np.float32), cache["attn"]


def one_chip(args) -> None:
    import jax

    from repro.common.config import EvictionConfig
    from repro.configs import get_config
    from repro.core.lookahead import init_lookahead_params
    from repro.kernels import ops
    from repro.models import transformer as tf
    from repro.serving import (ChunkingConfig, ContinuousEngine,
                               DecodeEvictionConfig, KVBlockPool,
                               ServingConfig)

    cfg = get_config("qwen2-1.5b")
    t = time.perf_counter()
    params = jax.jit(tf.init_params, static_argnums=1)(
        jax.random.PRNGKey(args.seed), cfg)
    lkv = init_lookahead_params(jax.random.PRNGKey(args.seed + 1), cfg,
                                params["layers"])
    jax.block_until_ready((params, lkv))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"model: {cfg.name} {cfg.num_layers}L d_model={cfg.d_model} "
        f"heads={cfg.attn.num_heads}/{cfg.attn.num_kv_heads} "
        f"vocab={cfg.vocab_size} params={n_params / 1e9:.3f}B bf16 "
        f"(init {time.perf_counter() - t:.1f}s)")

    rng = np.random.default_rng(args.seed)
    lengths, max_new, interval = PROMPT_LENS, MAX_NEW, SWEEP_INTERVAL
    reqs = make_requests(cfg, rng, lengths, max_new)
    pool = KVBlockPool(cfg, block_size=64, pool_mb=256)
    sc = ServingConfig(
        policy="lookaheadkv",
        evict=EvictionConfig(budget=256),
        decode_evict=DecodeEvictionConfig(enabled=True, interval=interval),
        chunking=ChunkingConfig(chunk=256, max_context=4096,
                                decode_chunk=8),
        num_slots=4, max_new_tokens=max(max_new), eos_id=-1,
        kv_pool=pool, capture_admission=True)
    eng = ContinuousEngine(params, cfg, sc, lkv_params=lkv)
    check(any(m > interval for m in max_new),
          "no request crosses the decode-eviction interval")

    done, cold = serve(eng, reqs, "serve (cold)")
    check_build(eng, "serve")
    sweeps = int(eng.metrics.value("serving_decode_evict_sweeps_total"))
    check(sweeps > 0, "no decode-eviction sweep ran")
    done, warm = serve(eng, reqs, "serve (warm)")
    toks = sum(len(r.out_tokens) for r in done)
    prompt_toks = sum(lengths)
    log(f"served {len(done)} requests ({prompt_toks} prompt + {toks} new "
        f"tokens), {sweeps} decode-eviction sweeps reclaimed "
        f"{pool.stats()['blocks_reclaimed_decode']} blocks")
    log(f"compile seconds (cold run minus warm run): {cold - warm:.1f}")
    log(f"serve seconds (warm run): {warm:.2f}  "
        f"(one-off bring-up reading, not a benchmark)")

    # every request's first-token logits: finite, and their argmax is the
    # token the engine served
    served = prefill_programs(eng, reference=False)
    first = {}
    for r in done:
        logits, _ = direct_prefill(eng, r, served)
        check(np.isfinite(logits).all(),
              f"request {r.uid}: non-finite first-token logits")
        check(int(np.argmax(logits[:cfg.vocab_size])) == r.out_tokens[0],
              f"request {r.uid}: served first token {r.out_tokens[0]} is "
              f"not the argmax of its prefill logits")
        first[r.uid] = logits
    log(f"first-token logits of all {len(done)} requests are finite and "
        f"their argmax is the served token")

    # the longest request against plain jnp under reference_mode
    r = max(done, key=lambda x: len(x.prompt))
    got = first[r.uid]
    with ops.reference_mode():
        ref, ref_cache = direct_prefill(
            eng, r, prefill_programs(eng, reference=True))
    check(np.isfinite(ref).all(), "reference logits are not finite")
    got, ref = got[:cfg.vocab_size], ref[:cfg.vocab_size]
    diff = float(np.max(np.abs(got - ref)))
    tol = LOGIT_RTOL * max(1.0, float(np.max(np.abs(ref))))
    ref_tok = int(np.argmax(ref))
    log(f"reference (request {r.uid}, {len(r.prompt)} prompt tokens): "
        f"served token {r.out_tokens[0]}, reference token {ref_tok}, "
        f"max|dlogit| {diff:.5f} (tolerance {tol:.5f}, "
        f"max|logit| {float(np.max(np.abs(ref))):.4f})")
    check(ref_tok == r.out_tokens[0],
          "served first token differs from the reference")
    check(diff <= tol, f"max|dlogit| {diff} exceeds {tol}")
    served, want = kept_sets(r.admission_cache), kept_sets(ref_cache)
    same = sum(served[k] == want[k] for k in want)
    inter = sum(len(served[k] & want[k]) for k in want)
    total = sum(len(want[k]) for k in want)
    log(f"kept sets agreeing with the reference: {same}/{len(want)} "
        f"(layer, kv head) sets identical, {inter}/{total} "
        f"({inter / max(total, 1):.4f}) kept positions shared")
    log(f"peak HBM (GiB): {peak_hbm_gib(jax.devices()[:1])}")


def four_chips(args) -> None:
    import jax

    from repro.common.config import EvictionConfig
    from repro.common.sharding import init_params_sharded
    from repro.configs import get_config
    from repro.core.lookahead import init_lookahead_params
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as tf
    from repro.serving import (ChunkingConfig, ContinuousEngine, KVBlockPool,
                               ServingConfig)

    check(len(jax.devices()) == 4, f"--four-chips needs 4 chips, found "
          f"{len(jax.devices())}")
    mesh = make_host_mesh(model=4)
    log(f"mesh: {dict(mesh.shape)}")
    rng = np.random.default_rng(args.seed)

    def build(cfg, params, *, sharded, pool_mb):
        lkv = init_lookahead_params(jax.random.PRNGKey(args.seed + 1), cfg,
                                    params["layers"])
        m = mesh if sharded else None
        pool = KVBlockPool(cfg, block_size=64, pool_mb=pool_mb, mesh=m)
        sc = ServingConfig(
            policy="lookaheadkv", evict=EvictionConfig(budget=256),
            chunking=ChunkingConfig(chunk=256, max_context=2048,
                                    decode_chunk=8),
            num_slots=2, max_new_tokens=32, eos_id=-1, kv_pool=pool,
            mesh=m, capture_admission=True)
        return ContinuousEngine(params, cfg, sc, lkv_params=lkv)

    # llama3-8b at full depth: ~16 GB of bf16 weights, one quarter per chip
    cfg = get_config("llama3-8b")
    t = time.perf_counter()
    params = init_params_sharded(jax.random.PRNGKey(args.seed), cfg, mesh)
    jax.block_until_ready(params)
    total = sum(x.nbytes for x in jax.tree.leaves(params))
    per_dev = {}
    for x in jax.tree.leaves(params):
        for s in x.addressable_shards:
            per_dev[s.device] = per_dev.get(s.device, 0) + s.data.nbytes
    log(f"model: {cfg.name} {cfg.num_layers}L params "
        f"{total / 2**30:.2f} GiB, per chip "
        f"{[round(b / 2**30, 2) for b in per_dev.values()]} GiB "
        f"(sharded init {time.perf_counter() - t:.1f}s)")
    check(max(per_dev.values()) < 0.5 * total,
          "params were materialized whole on one chip")
    reqs = make_requests(cfg, rng, [1024, 2000, 1500, 1800], [32] * 4)
    eng = build(cfg, params, sharded=True, pool_mb=1024)
    _, cold = serve(eng, reqs, "llama3-8b model=4 (cold)")
    check_build(eng, "llama3-8b model=4")
    _, warm = serve(eng, reqs, "llama3-8b model=4 (warm)")
    log(f"llama3-8b model=4: compile seconds {cold - warm:.1f}, serve "
        f"seconds {warm:.2f} (one-off bring-up reading)")
    del eng, params

    # identity: the same widths at 4 layers, one chip vs model=4
    cfg4 = dataclasses.replace(cfg, num_layers=4)
    key = jax.random.PRNGKey(args.seed)
    reqs = make_requests(cfg4, rng, [1024, 1900, 700], [32] * 3)
    p1 = jax.jit(tf.init_params, static_argnums=1)(key, cfg4)
    single, _ = serve(build(cfg4, p1, sharded=False, pool_mb=256),
                      reqs, "4-layer one chip")
    p4 = init_params_sharded(key, cfg4, mesh)
    eng4 = build(cfg4, p4, sharded=True, pool_mb=256)
    sharded, _ = serve(eng4, reqs, "4-layer model=4")
    check_build(eng4, "4-layer model=4")
    # report every difference before failing on any, so one run shows
    # what differs
    differ = []
    for a, b in zip(single, sharded):
        same_tok = sum(x == y for x, y in zip(a.out_tokens, b.out_tokens))
        line = f"request {a.uid}: {same_tok}/{len(a.out_tokens)} tokens"
        if a.out_tokens != b.out_tokens:
            differ.append(f"request {a.uid} tokens")
        for leaf in ("admission_cache", "retirement_cache"):
            ka, kb = kept_sets(getattr(a, leaf)), kept_sets(getattr(b, leaf))
            same = sum(ka[k] == kb[k] for k in ka)
            line += f", {leaf} {same}/{len(ka)} (layer, kv head) sets"
            if same != len(ka):
                differ.append(f"request {a.uid} {leaf}")
        log(f"4-layer one chip vs model=4, {line} identical")
    log(f"peak HBM per chip (GiB): {peak_hbm_gib(jax.devices())}")
    check(not differ, f"model=4 differs from one chip: {', '.join(differ)}")
    log(f"4-layer identity: tokens and admission/retirement kept sets of "
        f"{len(reqs)} requests identical on one chip and at model=4")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel four-chip path")
    args = ap.parse_args()

    import jax
    if jax.default_backend() != "tpu":
        fail(f"no TPU found (JAX backend: {jax.default_backend()})")
    from repro.common.compile_cache import enable_compile_cache
    cache = Path(enable_compile_cache())
    entries = len(list(cache.iterdir())) if cache.is_dir() else 0
    log(f"compile cache: {cache} ({entries} entries at start)")
    dev = jax.devices()[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    if args.four_chips:
        four_chips(args)
    else:
        one_chip(args)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
