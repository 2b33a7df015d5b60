"""Paged KV memory: allocator invariants, paged-vs-dense serving
bit-identity, preempt-to-queue, pool-backed prefix sharing, and
kernel-vs-oracle parity.

Four layers of proof, least to most end-to-end:

1. **Allocator invariants** (no model): the pool is conserved under
   adversarial alloc/free/incref/reserve interleavings, double-frees and
   null-block frees fail loudly, reservations fence ordinary allocations,
   and ``check()`` holds after every step.
2. **Kernel parity** (no engine): the Pallas block-table decode kernel
   matches the dense-gather oracle over randomized GQA shapes, ragged
   tables (null entries, null tails), per-head masks, and fully-masked
   tail blocks.
3. **Differential traces** (the headline): serving a seeded randomized
   trace through ``ContinuousEngine`` with a ``KVBlockPool`` emits
   *bit-identical tokens and kept (layer, head, position) sets* as dense
   serving — every servable single-pass policy, chunk sizes 128 and 256,
   prompts not divisible by the chunk, on both the jnp and forced-Pallas
   dispatch paths (the CI matrix runs this file under both).
4. **Memory pressure**: a deliberately tiny pool under burst arrivals
   (optimistic admission) preempts running requests to the queue — and
   the re-served tokens are still bit-identical, with the pool conserved
   and fully drained afterwards.  Pool-backed prefix-cache entries share
   the same pool without perturbing any of it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import sweep_cases
from repro.configs import get_smoke_config
from repro.core import policies
from repro.core.lookahead import init_lookahead_params
from repro.kernels import ops, ref
from repro.kernels.paged_attention import (paged_decode_attention_pallas,
                                           paged_decode_masses_pallas)
from repro.models import transformer as tf
from repro.serving import DecodeEvictionConfig, KVBlockPool, PrefixCache
from repro.serving.engine import paged_sweep
from trace_utils import kept_sets, make_trace_requests, run_trace

ENGINE_POLICIES = [p for p in policies.SINGLE_PASS
                   if p not in ("gt_oracle", "full")]


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("smollm-135m")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    lkv = init_lookahead_params(jax.random.PRNGKey(1), cfg, params["layers"])
    return cfg, params, lkv


def _pool(cfg, **kw):
    kw.setdefault("block_size", 16)
    kw.setdefault("num_blocks", 128)
    return KVBlockPool(cfg, **kw)


# ---------------------------------------------------------------------------
# 1. allocator invariants (no model forward passes)
# ---------------------------------------------------------------------------


def test_allocator_basics_and_double_free():
    cfg = get_smoke_config("smollm-135m")
    pool = _pool(cfg, num_blocks=8)
    assert pool.usable_blocks == 8 and pool.free_blocks() == 8
    assert pool.blocks_for(0) == 0
    assert pool.blocks_for(1) == 1
    assert pool.blocks_for(16) == 1
    assert pool.blocks_for(17) == 2
    a = pool.alloc(3)
    assert a is not None and len(a) == 3 and 0 not in a
    assert pool.used_blocks() == 3 and pool.high_water == 3
    assert pool.alloc(6) is None, "over-allocation must refuse, not split"
    pool.incref(a[:1])  # shared block survives one free
    pool.free(a)
    assert pool.used_blocks() == 1
    pool.free(a[:1])
    assert pool.used_blocks() == 0
    with pytest.raises(AssertionError):
        pool.free(a[:1])  # double-free
    with pytest.raises(AssertionError):
        pool.free([0])  # the null block is never allocatable
    pool.check()


def test_reservations_fence_ordinary_allocations():
    cfg = get_smoke_config("smollm-135m")
    pool = _pool(cfg, num_blocks=8)
    assert pool.reserve(5)
    assert pool.available_blocks() == 3
    assert pool.alloc(4) is None, "ordinary alloc dipped into a reservation"
    assert pool.alloc(3) is not None
    assert not pool.reserve(1), "over-promise accepted"
    got = pool.alloc(2, from_reserved=True)
    assert got is not None and pool.reserved == 3
    pool.unreserve(3)
    assert pool.reserved == 0
    pool.check()


def test_allocator_invariants_under_adversarial_interleavings():
    cfg = get_smoke_config("smollm-135m")
    for case in sweep_cases(7, 5, lambda r: {"seed": int(r.integers(1e6))}):
        rng = np.random.default_rng(case["seed"])
        pool = _pool(cfg, num_blocks=int(rng.integers(8, 32)))
        held: list[np.ndarray] = []   # refcount-1 runs
        shared: list[np.ndarray] = []  # runs holding an extra ref
        for _ in range(200):
            op = rng.integers(5)
            if op == 0:
                ids = pool.alloc(int(rng.integers(1, 4)))
                if ids is not None:
                    held.append(ids)
            elif op == 1 and held:
                pool.free(held.pop(int(rng.integers(len(held)))))
            elif op == 2 and held:
                ids = held[int(rng.integers(len(held)))]
                pool.incref(ids)
                shared.append(ids)
            elif op == 3 and shared:
                pool.free(shared.pop(int(rng.integers(len(shared)))))
            elif op == 4:
                if rng.random() < 0.5:
                    pool.reserve(int(rng.integers(0, 3)))
                elif pool.reserved:
                    pool.unreserve(1)
            pool.check()
        pool.unreserve(pool.reserved)
        for ids in shared:
            pool.free(ids)
        for ids in held:
            pool.free(ids)
        pool.check()
        assert pool.used_blocks() == 0, "pool not conserved after drain"


# ---------------------------------------------------------------------------
# 2. kernel-vs-oracle parity (ragged tables, masked tails, per-head masks)
# ---------------------------------------------------------------------------


def _paged_case(rng):
    kv = int(rng.choice([1, 2]))
    return {
        "B": int(rng.integers(1, 4)),
        "KV": kv,
        "G": int(rng.choice([1, 3])),
        "hd": int(rng.choice([16, 32])),
        "bs": int(rng.choice([4, 8, 16])),
        "N": int(rng.integers(4, 12)),
        "nb": int(rng.integers(1, 6)),
        "seed": int(rng.integers(1e6)),
    }


@pytest.mark.parametrize("case", sweep_cases(11, 8, _paged_case))
def test_paged_kernel_matches_oracle(case):
    rng = np.random.default_rng(case["seed"])
    B, KV, hd, bs = case["B"], case["KV"], case["hd"], case["bs"]
    N, nb, H = case["N"], case["nb"], case["KV"] * case["G"]
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    pk = jnp.asarray(rng.normal(size=(N, KV, bs, hd)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(N, KV, bs, hd)), jnp.float32)
    pm = jnp.asarray(rng.random((N, KV, bs)) > 0.3)
    pm = pm.at[0].set(False)  # the null block is permanently invalid
    # ragged tables: null tails and interleaved null entries
    tbl = np.zeros((B, nb), np.int32)
    for b in range(B):
        n_live = int(rng.integers(0, nb + 1))
        tbl[b, :n_live] = rng.choice(np.arange(1, N), n_live, replace=False)
        rng.shuffle(tbl[b])
    tbl = jnp.asarray(tbl)
    want = ref.paged_decode_attention(q, pk, pv, pm, tbl)
    got = paged_decode_attention_pallas(q, pk, pv, pm, tbl, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_paged_kernel_fully_masked_tail_blocks():
    """A table whose live blocks are followed by all-null (or fully masked)
    tail blocks must match the oracle — and an entirely dead sequence must
    come out exact-zero, not NaN."""
    rng = np.random.default_rng(0)
    B, H, KV, hd, bs, N, nb = 2, 4, 2, 32, 8, 6, 4
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    pk = jnp.asarray(rng.normal(size=(N, KV, bs, hd)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(N, KV, bs, hd)), jnp.float32)
    pm = jnp.ones((N, KV, bs), bool).at[0].set(False)
    pm = pm.at[5].set(False)  # an allocated-but-fully-masked block
    tbl = jnp.asarray([[1, 2, 5, 0], [0, 0, 0, 0]], jnp.int32)
    want = ref.paged_decode_attention(q, pk, pv, pm, tbl)
    got = paged_decode_attention_pallas(q, pk, pv, pm, tbl, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert np.all(np.asarray(got[1]) == 0.0), "dead sequence must be zeros"


def test_ops_paged_dispatch_matches_oracle():
    """The public wrapper agrees with the oracle on whichever path the
    environment dispatches (jnp gather here, the kernel under
    REPRO_FORCE_PALLAS=1 in the CI matrix)."""
    rng = np.random.default_rng(1)
    B, H, KV, hd, bs, N, nb = 2, 6, 2, 16, 4, 8, 5
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    pk = jnp.asarray(rng.normal(size=(N, KV, bs, hd)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(N, KV, bs, hd)), jnp.float32)
    pm = jnp.asarray(rng.random((N, KV, bs)) > 0.2).at[0].set(False)
    tbl = jnp.asarray(rng.integers(0, N, (B, nb)), jnp.int32)
    want = ref.paged_decode_attention(q, pk, pv, pm, tbl)
    got = ops.paged_decode_attention(q, pk, pv, pm, tbl)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# 3. differential traces: paged serving is bit-identical to dense
# ---------------------------------------------------------------------------


def _assert_paged_differential(cfg, params, lkv, *, policy, requests, chunk,
                               pool, **kw):
    base, _ = run_trace(cfg, params, lkv, policy=policy, requests=requests,
                        chunk=chunk, **kw)
    got, eng = run_trace(cfg, params, lkv, policy=policy, requests=requests,
                         chunk=chunk, kv_pool=pool, **kw)
    for uid, want in base.items():
        r = got[uid]
        assert r.out_tokens == want.out_tokens, \
            f"policy={policy} chunk={chunk} uid={uid}: tokens diverged"
        assert kept_sets(r.admission_cache) == kept_sets(
            want.admission_cache), \
            f"policy={policy} chunk={chunk} uid={uid}: kept sets diverged"
    return eng


@pytest.mark.parametrize("chunk", [128, 256])
@pytest.mark.parametrize("policy", ENGINE_POLICIES)
def test_paged_vs_dense_differential(model, policy, chunk):
    """Tokens and kept sets are bit-equal paged vs dense for every
    servable single-pass policy and both chunk sizes (mixed non-divisible
    prompt lengths)."""
    cfg, params, lkv = model
    reqs = make_trace_requests(cfg, chunk=chunk, seed=0, n_requests=4,
                               max_new=3)
    pool = _pool(cfg)
    eng = _assert_paged_differential(cfg, params, lkv, policy=policy,
                                     requests=reqs, chunk=chunk, pool=pool,
                                     decode_chunk=2)
    pool.check()
    assert pool.used_blocks() == 0, "retired requests must drain the pool"
    assert eng.stats["kv_pool"]["high_water_blocks"] > 0


def test_paged_differential_burst_concurrency(model):
    """Simultaneous arrivals exercise concurrent slots sharing the pool —
    zombie slots must never corrupt a neighbour's blocks."""
    cfg, params, lkv = model
    reqs = make_trace_requests(cfg, chunk=128, seed=2, n_requests=6,
                               max_new=5)
    for r in reqs:
        r.arrival_s = 0.0
    pool = _pool(cfg)
    eng = _assert_paged_differential(cfg, params, lkv, policy="h2o",
                                     requests=reqs, chunk=128, pool=pool,
                                     num_slots=4, decode_chunk=2)
    pool.check()
    assert pool.used_blocks() == 0
    assert eng.stats["max_concurrency"] >= 1


# ---------------------------------------------------------------------------
# 4. memory pressure: preemption, gated admission, prefix sharing
# ---------------------------------------------------------------------------


def test_preemption_under_tiny_pool_is_exact(model):
    """Optimistic admission over a pool that cannot hold every admitted
    request's growth: the engine must preempt to the queue, re-serve
    bit-identically, and leave the pool conserved."""
    cfg, params, lkv = model
    chunk = 128
    reqs = make_trace_requests(cfg, chunk=chunk, seed=5, n_requests=6,
                               max_new=8, suffix_lens=(0, 1, 77))
    for r in reqs:
        r.arrival_s = 0.0
    base, _ = run_trace(cfg, params, lkv, policy="streaming_llm",
                        requests=reqs, chunk=chunk, num_slots=3,
                        decode_chunk=1)
    # depth = budget(8) + margin(9) = 17 rows; 5 blocks of 4 rows per
    # request worst-case — 7 usable blocks admit two but can't grow both
    pool = _pool(cfg, block_size=4, num_blocks=7)
    got, eng = run_trace(cfg, params, lkv, policy="streaming_llm",
                         requests=reqs, chunk=chunk, num_slots=3,
                         decode_chunk=1, kv_pool=pool,
                         reserve_appends=False)
    for uid, want in base.items():
        assert got[uid].out_tokens == want.out_tokens, uid
        assert kept_sets(got[uid].admission_cache) == kept_sets(
            want.admission_cache), uid
    assert eng.stats["preemptions"] > 0, \
        "tiny pool under burst must exercise preempt-to-queue"
    pool.check()
    assert pool.used_blocks() == 0
    assert pool.reserved == 0


def test_pool_backed_prefix_cache_shares_and_reclaims(model):
    """Prefix-cache entries pinned as block runs in the serving pool:
    differential exactness holds, pins are accounted, and eviction
    returns every block."""
    cfg, params, lkv = model
    chunk = 128
    reqs = make_trace_requests(cfg, chunk=chunk, seed=3, n_requests=5,
                               max_new=3)
    base, _ = run_trace(cfg, params, lkv, policy="lookaheadkv",
                        requests=reqs, chunk=chunk, decode_chunk=2)
    pool = _pool(cfg, block_size=16, num_blocks=256)
    cache = PrefixCache(chunk=chunk, max_bytes=1 << 30, pool=pool)
    got, eng = run_trace(cfg, params, lkv, policy="lookaheadkv",
                         requests=reqs, chunk=chunk, kv_pool=pool,
                         prefix_cache=cache, decode_chunk=2)
    for uid, want in base.items():
        assert got[uid].out_tokens == want.out_tokens, uid
        assert kept_sets(got[uid].admission_cache) == kept_sets(
            want.admission_cache), uid
    s = cache.stats()
    assert s["hits"] > 0 and s["pool_blocks_pinned"] > 0
    assert pool.pinned_blocks == s["pool_blocks_pinned"]
    assert pool.used_blocks() == s["pool_blocks_pinned"], \
        "only prefix pins may outlive the trace"
    # live traffic reclaims cached prefixes on demand
    assert cache.evict_pool_blocks(s["pool_blocks_pinned"])
    pool.check()
    assert pool.used_blocks() == 0 and pool.pinned_blocks == 0


def test_reserve_failure_reclaims_prefix_blocks_no_livelock(model):
    """Regression: a pool whose free space is almost entirely prefix-cache
    pins must still admit under ``reserve_appends`` — the reserve-failure
    path reclaims cached prefixes instead of re-queueing the head forever
    (the admission gate counts evictable blocks as free, so giving up
    without evicting restores the exact pre-attempt state: a livelock)."""
    cfg, params, lkv = model
    chunk = 128
    reqs = make_trace_requests(cfg, chunk=chunk, seed=8, n_requests=3,
                               max_new=4, suffix_lens=(0, 1))
    base, _ = run_trace(cfg, params, lkv, policy="streaming_llm",
                        requests=reqs, chunk=chunk, decode_chunk=2)
    # depth = budget(8)+margin(5) = 13 rows -> 2 data + 2 append blocks of
    # 4 rows; the first admission's prefix inserts pin most of the pool,
    # so later admissions must evict cached spans to keep their promises
    pool = _pool(cfg, block_size=4, num_blocks=36)
    cache = PrefixCache(chunk=chunk, max_bytes=1 << 30, pool=pool)
    got, eng = run_trace(cfg, params, lkv, policy="streaming_llm",
                         requests=reqs, chunk=chunk, decode_chunk=2,
                         kv_pool=pool, prefix_cache=cache)
    for uid, want in base.items():
        assert got[uid].out_tokens == want.out_tokens, uid
    pool.check()
    assert pool.reserved == 0


def test_prefix_insert_skipped_when_pool_is_consumed(model):
    """A pool with no room for prefix spans must not break serving — the
    insert is skipped, traffic still serves exactly."""
    cfg, params, lkv = model
    chunk = 128
    reqs = make_trace_requests(cfg, chunk=chunk, seed=4, n_requests=3,
                               max_new=3)
    base, _ = run_trace(cfg, params, lkv, policy="h2o", requests=reqs,
                        chunk=chunk, decode_chunk=2)
    pool = _pool(cfg, block_size=16, num_blocks=6)  # decode fits, spans don't
    cache = PrefixCache(chunk=chunk, max_bytes=1 << 30, pool=pool)
    got, _ = run_trace(cfg, params, lkv, policy="h2o", requests=reqs,
                       chunk=chunk, kv_pool=pool, prefix_cache=cache,
                       decode_chunk=2)
    for uid, want in base.items():
        assert got[uid].out_tokens == want.out_tokens, uid
    pool.check()


# ---------------------------------------------------------------------------
# 5. observability
# ---------------------------------------------------------------------------


def test_pool_stats_and_engine_reporting(model):
    cfg, params, lkv = model
    reqs = make_trace_requests(cfg, chunk=128, seed=6, n_requests=3,
                               max_new=3)
    pool = _pool(cfg)
    got, eng = run_trace(cfg, params, lkv, policy="snapkv", requests=reqs,
                         chunk=128, kv_pool=pool, decode_chunk=2)
    s = eng.stats["kv_pool"]
    for key in ("blocks_total", "blocks_used", "blocks_free",
                "blocks_reserved", "blocks_pinned_prefix",
                "high_water_blocks", "bytes_total", "bytes_high_water",
                "queued", "preemptions"):
        assert key in s, key
    assert s["high_water_blocks"] > 0
    assert 0 < eng.stats["max_concurrency"] <= eng.num_slots
    cb = eng.cache_bytes(128)
    assert "pool" in cb and cb["evicted"] > 0
    assert eng.kv_device_bytes() == s["bytes_total"]


# ---------------------------------------------------------------------------
# 6. async-dispatch mirror snapshots
# ---------------------------------------------------------------------------


def test_mirror_snapshots_are_frozen_copies(model):
    """The paged dispatch hands jax *snapshots* of the host mirrors: jax
    stages host->device transfers lazily, so mutating a mirror in place
    after the call (cursor advance, retirement bookkeeping) must never
    change what an in-flight dispatch reads.  ``_snapshot`` hands jax a
    private read-only copy; the original mirror stays writable."""
    from repro.serving import engine as engine_mod

    a = np.arange(6, dtype=np.int32)
    snap = engine_mod._snapshot(a)
    a[:] = -1  # post-dispatch mirror mutation, as the engine does in place
    assert np.asarray(snap).tolist() == [0, 1, 2, 3, 4, 5]
    assert a.flags.writeable  # only the handed-off copy is frozen

    cfg, params, lkv = model
    reqs = make_trace_requests(cfg, chunk=128, seed=9, n_requests=2,
                               max_new=3)
    pool = _pool(cfg)
    _, eng = run_trace(cfg, params, lkv, policy="h2o", requests=reqs,
                       chunk=128, kv_pool=pool, decode_chunk=2)
    before = np.asarray(eng._table_dev).copy()
    eng._table_h[:] = 7  # the device snapshot must not alias the mirror
    assert np.array_equal(np.asarray(eng._table_dev), before)


# ---------------------------------------------------------------------------
# 7. decode-time streaming eviction on the paged pool
# ---------------------------------------------------------------------------


def test_paged_sweep_matches_numpy_topk():
    """The jitted evict-and-compact sweep against a from-scratch numpy
    reference: per (layer, kv head) keep the ``capacity`` highest-scoring
    valid rows in temporal order, compact them into the head blocks,
    zero the pad, carry kept score tallies, and touch *nothing* else —
    not other slots' score lanes, not blocks outside the keep run."""
    rng = np.random.default_rng(0)
    L, KV, hd, bs = 2, 2, 8, 4
    capacity, depth = 6, 16  # nb=4 blocks, nb_keep=2, pad rows 6..8 dead
    nb, nb_keep = 4, 2
    num_slots, N = 3, 12
    k = rng.normal(size=(L, N, KV, bs, hd)).astype(np.float32)
    v = rng.normal(size=(L, N, KV, bs, hd)).astype(np.float32)
    pos = rng.integers(0, 500, size=(L, N, KV, bs)).astype(np.int32)
    mask = rng.random((L, N, KV, bs)) < 0.8
    mask[:, 0] = False  # the null block
    score = rng.random((L, num_slots, depth, KV)).astype(np.float32)
    slot = 1
    table = np.zeros((num_slots, nb), np.int32)
    table[slot] = rng.choice(np.arange(1, N), nb, replace=False)
    newpool, newscore = paged_sweep(
        {"k": jnp.asarray(k), "v": jnp.asarray(v),
         "pos": jnp.asarray(pos), "mask": jnp.asarray(mask)},
        jnp.asarray(score), jnp.asarray(table),
        jnp.asarray(slot, jnp.int32), capacity=capacity, depth=depth,
        block_size=bs, nb_keep=nb_keep)
    newpool = {n: np.asarray(x) for n, x in newpool.items()}
    newscore = np.asarray(newscore)

    row = table[slot]
    keep_ids = row[:nb_keep]

    def dense(x, ids, rows):  # (L, N, KV, bs, ...) -> (L, rows, KV, ...)
        g = np.swapaxes(x[:, ids], 2, 3)
        return g.reshape((L, len(ids) * bs) + g.shape[3:])[:, :rows]

    kd, vd = dense(k, row, depth), dense(v, row, depth)
    pd, md = dense(pos, row, depth), dense(mask, row, depth)
    kn = dense(newpool["k"], keep_ids, nb_keep * bs)
    vn = dense(newpool["v"], keep_ids, nb_keep * bs)
    pn = dense(newpool["pos"], keep_ids, nb_keep * bs)
    mn = dense(newpool["mask"], keep_ids, nb_keep * bs)
    for lyr in range(L):
        for h in range(KV):
            s = np.where(md[lyr, :, h], score[lyr, slot, :, h], -np.inf)
            keep = np.sort(np.argsort(-s, kind="stable")[:capacity])
            kept = md[lyr, keep, h]
            assert np.array_equal(mn[lyr, :capacity, h], kept), (lyr, h)
            assert not mn[lyr, capacity:, h].any(), "pad rows must be dead"
            j = np.nonzero(kept)[0]
            src = keep[kept]
            assert np.array_equal(kn[lyr, j, h], kd[lyr, src, h])
            assert np.array_equal(vn[lyr, j, h], vd[lyr, src, h])
            assert np.array_equal(pn[lyr, j, h], pd[lyr, src, h])
            dead = np.setdiff1d(np.arange(nb_keep * bs), j)
            assert np.all(kn[lyr, dead, h] == 0.0), "evicted rows leak K"
            want_sc = np.zeros(depth, np.float32)
            want_sc[j] = score[lyr, slot, src, h]
            assert np.array_equal(newscore[lyr, slot, :, h], want_sc)
    others = [s for s in range(num_slots) if s != slot]
    assert np.array_equal(newscore[:, others], score[:, others]), \
        "sweep must not touch other slots' score lanes"
    untouched = np.setdiff1d(np.arange(N), keep_ids)
    for name, old in (("k", k), ("v", v), ("pos", pos), ("mask", mask)):
        assert np.array_equal(newpool[name][:, untouched], old[:, untouched]), \
            f"sweep rewrote {name} blocks outside the keep run"


def _masses_case(rng):
    case = _paged_case(rng)
    case["window"] = int(rng.integers(3, 30)) if rng.random() < 0.5 else 0
    return case


@pytest.mark.parametrize("case", sweep_cases(17, 8, _masses_case))
def test_paged_masses_kernel_matches_oracle(case):
    """The two-phase Pallas masses kernel: ``out`` bitwise-identical to
    the plain decode kernel (phase 0 is the unmodified flash recurrence),
    masses match the dense-gather oracle, masked rows carry exact zeros —
    over ragged tables, per-head masks, GQA shapes, and sliding windows."""
    rng = np.random.default_rng(case["seed"])
    B, KV, hd, bs = case["B"], case["KV"], case["hd"], case["bs"]
    N, nb, H = case["N"], case["nb"], case["KV"] * case["G"]
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    pk = jnp.asarray(rng.normal(size=(N, KV, bs, hd)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(N, KV, bs, hd)), jnp.float32)
    pm = jnp.asarray(rng.random((N, KV, bs)) > 0.3).at[0].set(False)
    tbl = np.zeros((B, nb), np.int32)
    for b in range(B):
        n_live = int(rng.integers(0, min(nb, N - 1) + 1))
        tbl[b, :n_live] = rng.choice(np.arange(1, N), n_live, replace=False)
        rng.shuffle(tbl[b])
    tbl = jnp.asarray(tbl)
    kw = {}
    if case["window"]:
        kw = {"pos_pool": jnp.asarray(rng.integers(0, 50, (N, KV, bs)),
                                      jnp.int32),
              "new_pos": jnp.asarray(rng.integers(20, 70, (B,)), jnp.int32),
              "window": case["window"]}
    plain = paged_decode_attention_pallas(q, pk, pv, pm, tbl,
                                          interpret=True, **kw)
    got_out, got_m = paged_decode_masses_pallas(q, pk, pv, pm, tbl,
                                                interpret=True, **kw)
    assert np.array_equal(np.asarray(got_out), np.asarray(plain)), \
        "score_masses must not perturb the attention output"
    want_m = ref.paged_decode_masses(q, pk, pm, tbl, **kw)
    np.testing.assert_allclose(got_m, want_m, atol=2e-5, rtol=2e-5)
    # masked rows contribute exact zeros, alive heads sum to ~1
    dead = ~np.repeat(np.moveaxis(np.asarray(
        ref.gather_paged(pm, tbl)), 2, 1), H // KV, axis=1)
    if case["window"]:
        pos = np.asarray(ref.gather_paged(kw["pos_pool"], tbl))
        oow = (np.asarray(kw["new_pos"])[:, None, None] - pos) >= \
            case["window"]
        dead |= np.repeat(np.moveaxis(oow, 2, 1), H // KV, axis=1)
    got_m = np.asarray(got_m)
    assert np.all(got_m[dead] == 0.0)
    sums = got_m.sum(axis=-1)
    alive = ~dead.all(axis=-1)
    np.testing.assert_allclose(sums[alive], 1.0, atol=1e-4)
    assert np.all(sums[~alive] == 0.0)


def test_paged_masses_streaming_and_dispatch():
    """The jnp streaming tier's second-pass masses and the public
    ``ops.paged_decode_attention(score_masses=True)`` dispatch: same
    oracle, ``out`` bitwise-unchanged, ``depth`` slices the mass width."""
    rng = np.random.default_rng(3)
    B, H, KV, hd, bs, N, nb = 2, 6, 2, 16, 4, 11, 5
    depth = 18  # non-multiple of bs: the engine's capacity+interval shape
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    pk = jnp.asarray(rng.normal(size=(N, KV, bs, hd)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(N, KV, bs, hd)), jnp.float32)
    pm = np.asarray(rng.random((N, KV, bs)) > 0.2)
    pm[0] = False
    # one private block run per sequence, with the engine's depth
    # invariant: rows past ``depth`` are masked False by construction
    # (appends clamp at depth) — the kernel tier's depth slice assumes it
    tbl = 1 + np.arange(B * nb, dtype=np.int32).reshape(B, nb)
    pm[tbl[:, -1], :, depth - (nb - 1) * bs:] = False
    pm, tbl = jnp.asarray(pm), jnp.asarray(tbl)
    want_m = ref.paged_decode_masses(q, pk, pm, tbl)
    out1 = ops._paged_decode_streaming(q, pk, pv, pm, tbl)
    out2, m2 = ops._paged_decode_streaming(q, pk, pv, pm, tbl,
                                           score_masses=True)
    assert np.array_equal(np.asarray(out2), np.asarray(out1))
    np.testing.assert_allclose(m2, want_m, atol=2e-5, rtol=2e-5)
    # the public wrapper, on whichever tier the environment dispatches
    out3 = ops.paged_decode_attention(q, pk, pv, pm, tbl, depth=depth)
    out4, m4 = ops.paged_decode_attention(q, pk, pv, pm, tbl, depth=depth,
                                          score_masses=True)
    assert np.array_equal(np.asarray(out4), np.asarray(out3)), \
        "score_masses must not change the dispatched output"
    assert m4.shape == (B, H, depth)
    # with ``depth`` the attention (and so the normalizer) runs over the
    # first ``depth`` rows only — compare against the depth-sliced oracle,
    # not a post-hoc slice of the full-width masses
    want_d = ref.paged_decode_masses(q, pk, pm, tbl, depth=depth)
    np.testing.assert_allclose(m4, want_d, atol=2e-5, rtol=2e-5)


def _retire_kept(req):
    """Kept (layer, head, position) sets at retirement, from the decode
    cache snapshot ``_on_retire`` captures (already clipped at the
    emitted-token horizon, so runs with different cache depths compare)."""
    rc = req.retirement_cache
    assert rc is not None, "capture_admission must stash retirement_cache"
    return kept_sets({"mask": rc["mask"][:, None], "pos": rc["pos"][:, None]})


def test_decode_evict_interval_inf_is_bitwise_noop(model):
    """The API contract: decode eviction enabled with an interval no
    generation reaches emits bitwise-identical tokens AND kept sets (at
    retirement, per request) as the eviction-disabled paged path — the
    score plumbing, the grown cache depth, and the sweep gate change
    nothing until a sweep actually fires."""
    cfg, params, lkv = model
    chunk, max_new = 64, 24
    reqs = make_trace_requests(cfg, chunk=chunk, seed=11, n_requests=3,
                               max_new=max_new)
    pool_a = _pool(cfg, block_size=4, num_blocks=256)
    base, _ = run_trace(cfg, params, lkv, policy="lookaheadkv",
                        requests=reqs, chunk=chunk, kv_pool=pool_a)
    pool_b = _pool(cfg, block_size=4, num_blocks=256)
    # chunk dispatch can overshoot a finishing request by up to the max
    # decode chunk (16) rows, so "infinite" must cover max_new + 16
    got, eng = run_trace(cfg, params, lkv, policy="lookaheadkv",
                         requests=reqs, chunk=chunk, kv_pool=pool_b,
                         decode_evict=DecodeEvictionConfig(
                             enabled=True, interval=max_new + 16))
    assert eng.stats["decode_evict_sweeps"] == 0, \
        "interval > generation length must never sweep"
    for uid, want in base.items():
        assert got[uid].out_tokens == want.out_tokens, uid
        assert _retire_kept(got[uid]) == _retire_kept(want), uid
    for p in (pool_a, pool_b):
        p.check()
        assert p.used_blocks() == 0
    assert pool_b.blocks_reclaimed_decode == 0


def test_decode_evict_sweeps_reclaim_mid_generation(model):
    """Active decode eviction: sweeps fire, whole blocks return to the
    pool mid-generation, every request still completes at full length,
    the per-slot footprint is bounded at capacity + interval rows, and
    the pool drains conserved afterwards."""
    cfg, params, lkv = model
    chunk, max_new = 64, 24
    reqs = make_trace_requests(cfg, chunk=chunk, seed=12, n_requests=4,
                               max_new=max_new)
    for r in reqs:
        r.arrival_s = 0.0
    pool = _pool(cfg, block_size=4, num_blocks=256)
    got, eng = run_trace(cfg, params, lkv, policy="lookaheadkv",
                         requests=reqs, chunk=chunk, kv_pool=pool,
                         num_slots=2,
                         decode_evict=DecodeEvictionConfig(enabled=True,
                                                           interval=8))
    assert eng._paged_depth == 8 + 8  # budget + interval bounds the slot
    assert eng.stats["decode_evict_sweeps"] > 0
    assert pool.blocks_reclaimed_decode > 0, \
        "interval spanning whole blocks must free real blocks"
    assert eng.stats["kv_pool"]["blocks_reclaimed_decode"] == \
        pool.blocks_reclaimed_decode
    for r in got.values():
        assert len(r.out_tokens) == max_new  # eos_id=-1: full generations
    pool.check()
    assert pool.used_blocks() == 0 and pool.reserved == 0


def test_decode_evict_contended_matches_isolated(model):
    """Slot isolation under eviction: a request served in a contended
    multi-slot engine emits the same tokens and retires with the same
    kept sets as the same request served alone — sweeps fire at fixed
    per-slot growth marks, so neighbours cannot perturb the cache."""
    cfg, params, lkv = model
    chunk, max_new = 64, 20
    de = DecodeEvictionConfig(enabled=True, interval=8)
    reqs = make_trace_requests(cfg, chunk=chunk, seed=13, n_requests=3,
                               max_new=max_new)
    for r in reqs:
        r.arrival_s = 0.0
    max_ctx = max(len(r.prompt) for r in reqs)
    got, eng = run_trace(cfg, params, lkv, policy="lookaheadkv",
                         requests=reqs, chunk=chunk, num_slots=2,
                         max_context=max_ctx, decode_evict=de,
                         kv_pool=_pool(cfg, block_size=4, num_blocks=256))
    assert eng.stats["decode_evict_sweeps"] > 0
    for r in reqs:
        solo, _ = run_trace(cfg, params, lkv, policy="lookaheadkv",
                            requests=[r], chunk=chunk, num_slots=1,
                            max_context=max_ctx, decode_evict=de,
                            kv_pool=_pool(cfg, block_size=4,
                                          num_blocks=256))
        assert got[r.uid].out_tokens == solo[r.uid].out_tokens, r.uid
        assert _retire_kept(got[r.uid]) == _retire_kept(solo[r.uid]), r.uid
