"""Gather-free paged flash-decode + fused sampling epilogue.

Four proof layers, least to most end-to-end:

1. **Windowed kernel parity**: the extended Pallas block-table decode
   kernel (sliding-window masking via scalar-prefetched ``new_pos`` /
   ``window`` and block-indexed ``pos`` tiles) matches the dense-gather
   oracle over randomized GQA shapes, per-head masks, ragged tables with
   null entries/tails, block sizes {16, 64, 128}, and traced (jitted)
   window operands; fully windowed-out sequences come out exact-zero.
2. **Dispatch-tier exactness**: the streaming jnp fallback reproduces the
   oracle, and the jnp gather tier of ``ops.paged_decode_attention`` —
   including the unaligned ``depth`` slice — is *bitwise* equal to the
   dense decode reduction it must replay (the paged-vs-dense serving
   contract of ``tests/test_kv_pool.py``).
3. **Sampling reference**: ``filter_logits`` top-k / top-p unit tests
   against hand-computed kept sets, identity when disabled, and the
   replay-determinism of ``fold_keys``.
4. **Fused-vs-host determinism**: ``decode_chunk`` with the fused
   sampling epilogue emits the same tokens as an eager host loop that
   pulls per-step logits and samples with the same folded keys; the
   serving engine reports which dispatch tier decoded.
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
from repro.kernels.paged_attention import paged_decode_attention_pallas
from repro.models import transformer as tf
from repro.serving import KVBlockPool
from trace_utils import make_trace_requests, run_trace

_HUGE = 1 << 30


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("smollm-135m")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    lkv = init_lookahead_params(jax.random.PRNGKey(1), cfg, params["layers"])
    return cfg, params, lkv


def _paged_inputs(rng, *, B, KV, G, hd, bs, nb, p_valid=0.7, ragged=True):
    """Randomized pool-layout decode inputs: per-head masks, positions,
    and a table with interleaved null entries plus null tails."""
    H, N = KV * G, 2 + B * nb
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    pk = jnp.asarray(rng.normal(size=(N, KV, bs, hd)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(N, KV, bs, hd)), jnp.float32)
    pm = jnp.asarray(rng.random((N, KV, bs)) < p_valid).at[0].set(False)
    pos = jnp.asarray(rng.integers(0, nb * bs, (N, KV, bs)), jnp.int32)
    tbl = np.zeros((B, nb), np.int32)
    for b in range(B):
        n_live = int(rng.integers(0, nb + 1)) if ragged else nb
        tbl[b, :n_live] = rng.choice(np.arange(1, N), n_live, replace=False)
        if ragged:
            rng.shuffle(tbl[b])
    new_pos = jnp.asarray(rng.integers(1, nb * bs + 1, B), jnp.int32)
    return q, pk, pv, pm, pos, jnp.asarray(tbl), new_pos


# ---------------------------------------------------------------------------
# 1. windowed kernel parity
# ---------------------------------------------------------------------------


def _win_case(rng):
    kv = int(rng.choice([1, 2]))
    return {
        "B": int(rng.integers(1, 4)),
        "KV": kv,
        "G": int(rng.choice([1, 3])),
        "hd": int(rng.choice([16, 32])),
        "nb": int(rng.integers(1, 6)),
        "window": int(rng.choice([0, 3, 17, _HUGE])),  # 0 encodes None
        "seed": int(rng.integers(1e6)),
    }


@pytest.mark.parametrize("bs", [16, 64, 128])
@pytest.mark.parametrize("case", sweep_cases(23, 6, _win_case))
def test_windowed_kernel_matches_oracle(case, bs):
    rng = np.random.default_rng(case["seed"])
    q, pk, pv, pm, pos, tbl, npos = _paged_inputs(
        rng, B=case["B"], KV=case["KV"], G=case["G"], hd=case["hd"],
        bs=bs, nb=case["nb"])
    win = case["window"] or None
    kw = ({} if win is None
          else dict(pos_pool=pos, new_pos=npos, window=win))
    want = ref.paged_decode_attention(q, pk, pv, pm, tbl, **kw)
    got = paged_decode_attention_pallas(q, pk, pv, pm, tbl,
                                        interpret=True, **kw)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_windowed_kernel_traced_window():
    """The window arrives as a *traced* scalar (patterned local:global
    archs pass ``layer_window`` through jit) — the kernel must accept it
    without retracing per value and still match the oracle."""
    rng = np.random.default_rng(7)
    q, pk, pv, pm, pos, tbl, npos = _paged_inputs(
        rng, B=2, KV=2, G=2, hd=32, bs=16, nb=3)

    @jax.jit
    def f(w):
        return paged_decode_attention_pallas(
            q, pk, pv, pm, tbl, pos_pool=pos, new_pos=npos, window=w,
            interpret=True)

    for w in (5, 16, _HUGE):
        want = ref.paged_decode_attention(
            q, pk, pv, pm, tbl, pos_pool=pos, new_pos=npos, window=w)
        np.testing.assert_allclose(f(jnp.int32(w)), want,
                                   atol=2e-5, rtol=2e-5)


def test_windowed_out_sequence_is_exact_zero():
    """Every key older than the window (and the all-null second row) must
    produce exact zeros — not NaN from an empty softmax."""
    rng = np.random.default_rng(3)
    q, pk, pv, pm, pos, tbl, _ = _paged_inputs(
        rng, B=2, KV=1, G=4, hd=16, bs=16, nb=2, p_valid=1.0, ragged=False)
    tbl = tbl.at[1].set(0)
    npos = jnp.asarray([1000, 1000], jnp.int32)  # window excludes all pos
    for fn in (ref.paged_decode_attention,
               lambda *a, **k: paged_decode_attention_pallas(
                   *a, interpret=True, **k)):
        out = fn(q, pk, pv, pm, tbl, pos_pool=pos, new_pos=npos, window=4)
        assert np.all(np.asarray(out) == 0.0)


# ---------------------------------------------------------------------------
# 2. dispatch-tier exactness (streaming fallback, gather oracle, depth)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [None, 9, _HUGE])
def test_streaming_fallback_matches_oracle(window):
    """The jnp streaming block scan (the beyond-2k tier) reproduces the
    gather oracle under per-head masks, raggedness, and windows."""
    rng = np.random.default_rng(11)
    q, pk, pv, pm, pos, tbl, npos = _paged_inputs(
        rng, B=3, KV=2, G=3, hd=32, bs=16, nb=4)
    kw = ({} if window is None
          else dict(pos_pool=pos, new_pos=npos, window=window))
    want = ref.paged_decode_attention(q, pk, pv, pm, tbl, **kw)
    got = ops._paged_decode_streaming(q, pk, pv, pm, tbl, **kw)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.skipif(ops.use_pallas(), reason="bitwise dense equality is "
                    "the jnp gather tier's contract; the kernel tier is "
                    "covered by allclose parity + the differential traces")
@pytest.mark.parametrize("depth_off", [0, 1, 7])
def test_gather_tier_bitwise_equals_dense_reduction(depth_off):
    """``ops.paged_decode_attention`` on the jnp path — including an
    unaligned ``depth`` slice — must be *bit-identical* to gathering a
    dense view and running the dense decode reduction, because that is
    what keeps paged serving token-exact vs the dense engine."""
    rng = np.random.default_rng(13)
    B, KV, G, hd, bs, nb = 2, 2, 2, 32, 16, 4
    q, pk, pv, pm, pos, tbl, npos = _paged_inputs(
        rng, B=B, KV=KV, G=G, hd=hd, bs=bs, nb=nb, p_valid=0.95,
        ragged=False)
    depth = nb * bs - depth_off
    window = 24
    got = ops.paged_decode_attention(
        q, pk, pv, pm, tbl, pos_pool=pos, new_pos=npos, window=window,
        depth=depth)
    # the dense replay: gather, slice to depth, window on gathered pos
    def dense(pool):  # (N, KV, bs, ...) -> (B, depth, KV, ...)
        g = jnp.swapaxes(pool[tbl], 2, 3)
        return g.reshape((B, nb * bs) + g.shape[3:])[:, :depth]

    k, v, m, p = dense(pk), dense(pv), dense(pm), dense(pos)
    m = m & ((npos[:, None, None] - p) < window)
    want = ops.decode_attention(q, k, v, kv_mask=m)
    assert np.array_equal(np.asarray(got), np.asarray(want)), \
        "jnp gather tier drifted from the dense reduction"


def test_paged_decode_path_tiers():
    small, big = 1024, ops._DIRECT_SEQ + 1
    if ops.use_pallas():
        assert ops.paged_decode_path(small) == "kernel"
        assert ops.paged_decode_path(big) == "kernel"
    else:
        assert ops.paged_decode_path(small) == "gather"
        assert ops.paged_decode_path(big) == "fallback"


# ---------------------------------------------------------------------------
# 3. sampling reference: filter_logits / fold_keys / sample_logits
# ---------------------------------------------------------------------------


def test_filter_logits_top_k():
    logits = jnp.asarray([[5.0, 1.0, 4.0, 3.0, 2.0]])
    out = np.asarray(policies.filter_logits(logits, top_k=2))
    assert out[0, 0] == 5.0 and out[0, 2] == 4.0
    assert (out[0, [1, 3, 4]] <= -1e29).all()
    # ties at the k-th value are all kept (the filter never breaks ties
    # arbitrarily, so results don't depend on sort stability)
    tied = jnp.asarray([[3.0, 3.0, 3.0, 1.0]])
    out = np.asarray(policies.filter_logits(tied, top_k=2))
    assert (out[0, :3] == 3.0).all() and out[0, 3] <= -1e29


def test_filter_logits_top_p():
    probs = np.array([0.5, 0.3, 0.15, 0.05])
    logits = jnp.log(jnp.asarray(probs, jnp.float32))[None]
    kept = np.asarray(logits)[0]  # kept entries pass through unchanged
    # mass before: 0, .5, .8, .95 -> top_p=0.7 keeps the first two
    out = np.asarray(policies.filter_logits(logits, top_p=0.7))
    assert np.isfinite(out[0, :2]).all() and (out[0, 2:] <= -1e29).all()
    np.testing.assert_array_equal(out[0, :2], kept[:2])
    # a tiny top_p still keeps the argmax
    out = np.asarray(policies.filter_logits(logits, top_p=1e-6))
    assert out[0, 0] == kept[0] and (out[0, 1:] <= -1e29).all()


def test_filter_logits_disabled_is_identity():
    logits = jax.random.normal(jax.random.PRNGKey(0), (3, 17))
    out = policies.filter_logits(logits, top_k=0, top_p=1.0)
    assert out is logits, "disabled filters must be a python-level no-op"
    # top_k >= V is likewise identity (cheap common case)
    out = np.asarray(policies.filter_logits(logits, top_k=17))
    np.testing.assert_array_equal(out, np.asarray(logits))


def test_fold_keys_replay_determinism():
    seeds = jnp.asarray([3, 3, 9], jnp.int32)
    pos = jnp.asarray([10, 11, 10], jnp.int32)
    k1, k2 = policies.fold_keys(seeds, pos), policies.fold_keys(seeds, pos)
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(k2))
    # distinct (seed, position) pairs give distinct keys
    ks = np.asarray(k1).reshape(3, -1)
    assert len({tuple(r) for r in ks}) == 3


def test_sample_logits_greedy_is_argmax():
    logits = jax.random.normal(jax.random.PRNGKey(1), (4, 33))
    keys = policies.fold_keys(jnp.arange(4, dtype=jnp.int32),
                              jnp.zeros(4, jnp.int32))
    out = policies.sample_logits(logits, keys, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jnp.argmax(logits, -1)))


def test_sample_logits_respects_filters():
    """At temperature > 0 with a tight top-k, samples stay inside the
    kept set for every key."""
    logits = jnp.asarray(np.random.default_rng(5).normal(size=(64, 50)),
                         jnp.float32)
    keys = policies.fold_keys(jnp.arange(64, dtype=jnp.int32),
                              jnp.full(64, 7, jnp.int32))
    ids = np.asarray(policies.sample_logits(
        logits, keys, temperature=1.3, top_k=3))
    top3 = np.asarray(jax.lax.top_k(logits, 3)[1])
    assert all(ids[i] in top3[i] for i in range(64))


# ---------------------------------------------------------------------------
# 4. fused-vs-host sampling determinism + engine dispatch stats
# ---------------------------------------------------------------------------


def test_fused_sampling_matches_host_loop(model):
    """The fused epilogue inside jitted ``decode_chunk`` and an eager host
    loop (per-step logits transfers + the same folded keys) must emit the
    same token sequences — the epilogue changes where sampling runs, not
    what it samples."""
    cfg, params, lkv = model
    rng = np.random.default_rng(17)
    B, S, steps = 2, 24, 8
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    seeds = jnp.asarray([101, 202], jnp.int32)
    sampling = policies.Sampling(temperature=0.8, top_k=20, top_p=0.95)

    def fresh_state():
        pf = tf.prefill(params, cfg, prompts, policy="lookaheadkv",
                        lkv_params=lkv, extra_slots=steps + 1)
        keys = policies.fold_keys(seeds, jnp.full((B,), S, jnp.int32))
        first = policies.sample_logits(
            pf.logits, keys, temperature=sampling.temperature,
            top_k=sampling.top_k, top_p=sampling.top_p)[:, None]
        return first.astype(jnp.int32), pf.cache

    tok, cache = fresh_state()
    fused = jax.jit(lambda t, c, s: policies.decode_chunk(
        params, cfg, t, c, steps, sampling=sampling, seeds=s))
    _, _, toks_fused = fused(tok, cache, seeds)

    tok, cache = fresh_state()
    host = []
    for _ in range(steps):
        nxt_pos = cache["next_pos"][:, 0] + 1
        logits, cache = tf.decode_step(params, cfg, tok, cache)
        keys = policies.fold_keys(seeds, nxt_pos)
        tok = policies.sample_logits(
            logits, keys, temperature=sampling.temperature,
            top_k=sampling.top_k, top_p=sampling.top_p
        )[:, None].astype(jnp.int32)
        host.append(np.asarray(tok[:, 0]))
    np.testing.assert_array_equal(np.asarray(toks_fused),
                                  np.stack(host, axis=1))


def test_fused_sampling_same_seed_same_tokens(model):
    """Two runs, same seeds -> identical tokens; a different seed moves at
    least one of them (sanity that sampling is actually stochastic)."""
    cfg, params, lkv = model
    rng = np.random.default_rng(19)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 16)),
                          jnp.int32)
    sampling = policies.Sampling(temperature=1.0, top_k=0, top_p=1.0)
    fused = jax.jit(lambda t, c, s: policies.decode_chunk(
        params, cfg, t, c, 12, sampling=sampling, seeds=s)[2])

    def run(seed):
        pf = tf.prefill(params, cfg, prompts, policy="lookaheadkv",
                        lkv_params=lkv, extra_slots=13)
        tok = jnp.argmax(pf.logits, -1)[:, None].astype(jnp.int32)
        return np.asarray(fused(tok, pf.cache,
                                jnp.asarray([seed], jnp.int32)))

    a, b, c = run(42), run(42), run(43)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c), "seed must matter at temperature 1"


def test_engine_reports_decode_path_and_step_time(model):
    """The serving engine's stats must name the active dispatch tier and
    account decode wall time per step — paged and dense."""
    cfg, params, lkv = model
    chunk = 128
    reqs = make_trace_requests(cfg, chunk=chunk, seed=2, n_requests=3,
                               max_new=4)
    _, dense = run_trace(cfg, params, lkv, policy="lookaheadkv",
                         requests=reqs, chunk=chunk, decode_chunk=2)
    # a kernel backend names the jnp tier its per-head-mask decode takes
    assert dense.stats["decode_path"] == ("dense-jnp" if ops.use_pallas()
                                          else "dense")
    pool = KVBlockPool(cfg, block_size=16, num_blocks=128)
    _, paged = run_trace(cfg, params, lkv, policy="lookaheadkv",
                         requests=reqs, chunk=chunk, decode_chunk=2,
                         kv_pool=pool)
    assert paged.stats["decode_path"] == ops.paged_decode_path(
        paged._paged_depth)
    for eng in (dense, paged):
        assert eng.stats["decode_steps"] > 0
        assert eng.stats["decode_time_s"] > 0.0
