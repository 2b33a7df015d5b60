"""Eviction invariants (property tests, seeded sweeps):

  P1  budget is always respected — exactly ``capacity`` slots, validity mask
      bounds the per-layer budget;
  P2  retained indices are unique per (batch, kv head) and temporally sorted;
  P3  eviction at full budget is a no-op: decode attention over the evicted
      cache equals attention over the raw KV;
  P4  StreamingLLM keeps sink + most-recent tokens;
  P5  SnapKV-style window force-keep retains the observation suffix;
  P6  PyramidKV budgets: monotone decreasing, mean == budget;
  P7  maxpool is monotone, idempotent on constants, and dominates identity;
  P8  L1 normalization: sums to 1, scale-invariant;
  P9  KL ≥ 0 and == 0 iff identical distributions.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import sweep_cases
from repro.core import eviction as ev
from repro.core import scoring
from repro.core.objective import kl_divergence
from repro.kernels import ref


def _case(rng):
    return dict(B=int(rng.integers(1, 4)), KV=int(rng.integers(1, 4)),
                n=int(rng.integers(16, 100)),
                budget=int(rng.integers(2, 14)),
                seed=int(rng.integers(1 << 30)))


@pytest.mark.parametrize("case", sweep_cases(21, 10, _case))
def test_budget_respected_and_indices_unique(case):
    key = jax.random.PRNGKey(case["seed"])
    scores = jax.random.uniform(key, (case["B"], case["KV"], case["n"]))
    idx, mask = ev.select_topk(scores, case["budget"])
    assert idx.shape == (case["B"], case["KV"], case["budget"])
    assert bool(mask.all())  # uniform budgets: every slot valid
    for b in range(case["B"]):
        for h in range(case["KV"]):
            sel = np.asarray(idx[b, h])
            assert len(set(sel.tolist())) == len(sel)  # P2 unique
            assert (np.diff(sel) > 0).all()  # P2 sorted by position
    # P1 with a traced layer budget
    lb = jnp.asarray(max(case["budget"] - 1, 1))
    idx2, mask2 = ev.select_topk(scores, case["budget"], layer_budget=lb)
    assert int(mask2.sum()) == case["B"] * case["KV"] * int(lb)


@pytest.mark.parametrize("case", sweep_cases(22, 6, _case))
def test_full_budget_eviction_is_noop(case):
    """P3: evict with capacity >= n, then decode-attend: identical output."""
    key = jax.random.PRNGKey(case["seed"])
    ks = jax.random.split(key, 4)
    B, KV, n = case["B"], case["KV"], case["n"]
    hd, G = 16, 2
    k = jax.random.normal(ks[0], (B, n, KV, hd))
    v = jax.random.normal(ks[1], (B, n, KV, hd))
    q = jax.random.normal(ks[2], (B, KV * G, hd))
    scores = jax.random.uniform(ks[3], (B, KV, n))
    cache = ev.evict_layer(scores, k, v, capacity=n)
    # same *set* of (k, v) rows per head => same attention output
    out_full = ref.decode_attention(q, k, v)
    out_ev = ref.decode_attention(q, cache.k, cache.v,
                                  kv_mask=cache.mask)
    np.testing.assert_allclose(out_ev, out_full, atol=1e-5, rtol=1e-5)


def test_streaming_llm_keeps_sink_and_recent():
    B, KV, n, budget, sink = 2, 3, 64, 10, 4
    s = ev.position_scores("streaming_llm", n, B, KV, sink=sink)
    idx, mask = ev.select_topk(s, budget)
    want = set(range(sink)) | set(range(n - (budget - sink), n))
    for b in range(B):
        for h in range(KV):
            assert set(np.asarray(idx[b, h]).tolist()) == want


def test_window_force_keep():
    B, KV, n, budget, window = 1, 2, 64, 12, 8
    key = jax.random.PRNGKey(0)
    s = jax.random.uniform(key, (B, KV, n))
    s = ev.keep_window(s, window)
    idx, _ = ev.select_topk(s, budget)
    kept = set(np.asarray(idx[0, 0]).tolist())
    assert set(range(n - window, n)) <= kept


def test_pyramid_budgets():
    L, budget = 28, 128
    b = np.asarray(ev.pyramid_budgets(L, budget, beta=2.0))
    assert (np.diff(b) <= 0).all()
    assert abs(b.mean() - budget) / budget < 0.02
    assert b[0] > budget > b[-1]


@pytest.mark.parametrize("case", sweep_cases(23, 6, _case))
def test_maxpool_properties(case):
    key = jax.random.PRNGKey(case["seed"])
    s = jax.random.uniform(key, (case["B"], case["KV"], case["n"]))
    p = scoring.maxpool1d(s, 7)
    assert p.shape == s.shape
    assert bool((p >= s - 1e-7).all())  # dominates identity
    const = jnp.ones_like(s) * 0.3
    np.testing.assert_allclose(scoring.maxpool1d(const, 7), const)
    assert np.allclose(scoring.maxpool1d(s, 1), s)


@pytest.mark.parametrize("case", sweep_cases(24, 6, _case))
def test_normalize_and_kl(case):
    key = jax.random.PRNGKey(case["seed"])
    k1, k2 = jax.random.split(key)
    s = jax.random.uniform(k1, (case["B"], case["KV"], case["n"])) + 1e-3
    ns = scoring.normalize_l1(s)
    np.testing.assert_allclose(ns.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(scoring.normalize_l1(s * 7.3), ns, atol=1e-5)
    t = jax.random.uniform(k2, s.shape) + 1e-3
    nt = scoring.normalize_l1(t)
    assert bool((kl_divergence(ns, nt) >= -1e-6).all())  # P9 nonneg
    np.testing.assert_allclose(kl_divergence(ns, ns), 0.0, atol=1e-5)


def test_gqa_reduce():
    B, KV, G, n = 2, 3, 4, 10
    s = jnp.arange(B * KV * G * n, dtype=jnp.float32).reshape(B, KV * G, n)
    r = scoring.gqa_reduce(s, KV)
    assert r.shape == (B, KV, n)
    np.testing.assert_allclose(
        r[0, 0], s[0, 0:G].mean(0), atol=1e-5)


def test_gather_kv_zeroes_invalid():
    key = jax.random.PRNGKey(0)
    B, n, KV, hd = 1, 16, 1, 4
    k = jax.random.normal(key, (B, n, KV, hd))
    v = jax.random.normal(key, (B, n, KV, hd))
    scores = jnp.ones((B, KV, n))
    cache = ev.evict_layer(scores, k, v, capacity=8,
                           layer_budget=jnp.asarray(5))
    assert int(cache.mask.sum()) == 5
    masked = np.asarray(cache.k)[~np.asarray(cache.mask)]
    assert (masked == 0).all()


def _element_gather(k, v, idx, mask):
    """The element-indexed gather ``gather_kv`` replaced: the row index
    broadcast across ``head_dim``, one index per scalar."""
    B, S, KV, hd = k.shape
    cap = idx.shape[-1]
    ik = jnp.broadcast_to(jnp.swapaxes(idx, 1, 2)[..., None], (B, cap, KV, hd))
    m = jnp.swapaxes(mask, 1, 2)
    kk = jnp.where(m[..., None], jnp.take_along_axis(k, ik, axis=1), 0)
    vv = jnp.where(m[..., None], jnp.take_along_axis(v, ik, axis=1), 0)
    return ev.EvictedKV(k=kk, v=vv, pos=jnp.swapaxes(idx, 1, 2), mask=m)


@pytest.mark.parametrize("variant", ["plain", "padded", "layer_budget",
                                     "head_budgets", "key_mask",
                                     "extra_slots"])
@pytest.mark.parametrize("KV", [1, 2, 4])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_row_gather_matches_element_gather(monkeypatch, dtype, KV, variant):
    """``evict_layer``'s row gather is bit for bit the element gather:
    the same kept rows, zeroed invalid slots, positions and masks."""
    B, n, hd, cap = 2, 48, 16, 20
    ks = jax.random.split(jax.random.PRNGKey(KV), 4)
    k = jax.random.normal(ks[0], (B, n, KV, hd)).astype(dtype)
    v = jax.random.normal(ks[1], (B, n, KV, hd)).astype(dtype)
    scores = jax.random.uniform(ks[2], (B, KV, n))
    kw: dict = {}
    if variant == "padded":
        cap = n + 8  # capacity above the prompt: padded slots
    elif variant == "layer_budget":
        kw["layer_budget"] = jnp.asarray(13)
    elif variant == "head_budgets":
        kw["head_budgets"] = jax.random.randint(ks[3], (B, KV), 4, cap + 1)
    elif variant == "key_mask":
        cap = n  # selects padded keys, which come out masked
        kw["key_mask"] = jnp.arange(n)[None, :] < jnp.asarray([[n - 9], [31]])
    elif variant == "extra_slots":
        kw["extra_slots"] = 7
    got = ev.evict_layer(scores, k, v, cap, **kw)
    monkeypatch.setattr(ev, "gather_kv", _element_gather)
    want = ev.evict_layer(scores, k, v, cap, **kw)
    assert int((~got.mask).sum()) > 0 or variant in ("plain", "head_budgets")
    for name, a, b in zip(got._fields, got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert a.tobytes() == b.tobytes(), name


def test_adaptive_head_budgets_pool_invariant():
    """Ada-KV allocation: per-head budgets vary with score concentration but
    the global pool KV·budget is preserved (±KV from int rounding)."""
    key = jax.random.PRNGKey(42)
    B, KV, n, budget, cap = 3, 4, 64, 12, 24
    # head 0: spiky scores; head 3: flat
    base = jax.random.uniform(key, (B, KV, n)) * 0.1
    spike = base.at[:, 0, :3].add(5.0)
    b = ev.adaptive_head_budgets(spike, budget, cap)
    assert b.shape == (B, KV)
    assert bool((b >= 4).all()) and bool((b <= cap).all())
    np.testing.assert_allclose(np.asarray(b.sum(axis=1)), KV * budget,
                               atol=KV)
    # the spiky head gets more than the flat ones
    assert bool((b[:, 0] >= b[:, 3]).all())


def test_select_topk_per_head_respects_budgets():
    key = jax.random.PRNGKey(7)
    B, KV, n, cap = 2, 3, 40, 16
    scores = jax.random.uniform(key, (B, KV, n))
    hb = jnp.asarray([[4, 8, 12], [16, 5, 9]], jnp.int32)
    idx, mask = ev.select_topk_per_head(scores, cap, hb)
    np.testing.assert_array_equal(np.asarray(mask.sum(-1)), np.asarray(hb))
    # valid indices are the true top-k of each head
    for b in range(B):
        for h in range(KV):
            got = set(np.asarray(idx[b, h])[np.asarray(mask[b, h])].tolist())
            want = set(np.argsort(-np.asarray(scores[b, h]))
                       [: int(hb[b, h])].tolist())
            assert got == want


def test_adaptive_uniform_equivalence_when_flat():
    """With perfectly uniform scores every head gets ~the same budget."""
    B, KV, n, budget, cap = 1, 4, 64, 12, 24
    scores = jnp.ones((B, KV, n))
    b = ev.adaptive_head_budgets(scores, budget, cap)
    assert int(b.max() - b.min()) <= 1
