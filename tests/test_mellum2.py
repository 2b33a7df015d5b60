"""Mellum2's three mechanisms in the program, at a tiny Mellum2-shaped size
(``repro.configs.mellum2_12b.smoke``: two periods of sliding x3 then full,
8 experts held of 16, window 32):

- a layer holding a share of the experts: the shares of one layer, each
  held on its own, add up to the uncut layer;
- YaRN rotary on the full layers: frequencies and attention factor at
  Mellum2's published settings equal an independent formula;
- static windows in the kernel calls: dispatched by layer kind, the chunk
  attention and lookahead scoring give the logits of the traced-window
  calls the program made before (gemma3-tiny, Mellum2-tiny), on the jnp
  path and in Pallas interpret mode, and reach the kernels;
- the serving engine: every layer on the Pallas path (``score_path``) and
  ``moe.routed_rows`` counting each prompt row's top-k once per layer.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import EvictionConfig
from repro.configs import get_config, get_smoke_config
from repro.configs.mellum2_12b import YARN
from repro.core.lookahead import init_lookahead_params
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import rope
from repro.models import transformer as tf
from repro.serving import (ChunkingConfig, ContinuousEngine, KVBlockPool,
                           Request, ServingConfig)


def f32(cfg):
    return dataclasses.replace(cfg, dtype="float32")


# -- held experts -------------------------------------------------------------


def test_expert_shares_add_up_to_the_uncut_layer():
    """Each of 8 shares holds 2 of the 16 experts, routes over all 16 and
    adds its own experts' gated outputs; the 8 outputs sum to the layer
    that holds all 16."""
    base = f32(get_smoke_config("mellum2-12b"))
    whole = dataclasses.replace(
        base, moe=dataclasses.replace(base.moe, experts_held=0))
    p = moe_mod.init(jax.random.PRNGKey(0), whole)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 40, whole.d_model))
    full, _ = moe_mod.apply(p, whole, h)
    total = jnp.zeros_like(full)
    for i in range(8):
        cfg = dataclasses.replace(base, moe=dataclasses.replace(
            base.moe, experts_held=2, first_expert=2 * i))
        share = dict(p, experts=jax.tree.map(lambda w: w[2 * i:2 * i + 2],
                                             p["experts"]))
        out, _ = moe_mod.apply(share, cfg, h)
        total = total + out
    np.testing.assert_allclose(np.asarray(total), np.asarray(full),
                               rtol=1e-5, atol=1e-6)


def test_routed_rows_count_each_held_pick():
    base = get_smoke_config("mellum2-12b")
    p = moe_mod.init(jax.random.PRNGKey(0), base)
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 24, base.d_model))
    rows = jnp.arange(24)[None] < 20
    _, _, counts = moe_mod.apply(p, base, h, count_rows=rows)
    comb, _, _, idx = moe_mod._route(p, base, h)
    held = (idx[0, :20] < base.moe.held).sum(0)  # picks of experts 0..7
    assert counts.shape == (base.moe.held,)
    assert int(counts.sum()) == int(held.sum())


# -- YaRN ---------------------------------------------------------------------


def yarn_numpy(hd, theta, factor, orig, fast, slow):
    """YaRN inverse frequencies written from arXiv:2309.00071 section 3.2
    (ramp ends in pairs, rounded outwards), in float64."""
    i = np.arange(hd // 2, dtype=np.float64)
    base = theta ** (-2 * i / hd)

    def pair(rotations):  # the pair that turns `rotations` times over orig
        return hd * np.log(orig / (rotations * 2 * np.pi)) / (2 * np.log(theta))

    lo, hi = math.floor(pair(fast)), math.ceil(pair(slow))
    gamma = 1 - np.clip((i - lo) / (hi - lo), 0, 1)  # 1 below the ramp
    return gamma * base + (1 - gamma) * base / factor, (lo, hi)


def test_yarn_frequencies_at_mellum2_settings():
    theta, hd = 5e5, 128
    want, (lo, hi) = yarn_numpy(hd, theta, 16.0, 8192, 32.0, 1.0)
    assert (lo, hi) == (18, 35) == rope.yarn_ramp(hd, theta, YARN)
    got = np.asarray(rope.yarn_freqs(hd, theta, YARN), np.float64)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    plain = np.asarray(rope.rope_freqs(hd, theta), np.float64)
    np.testing.assert_array_equal(got[:lo + 1], plain[:lo + 1])
    np.testing.assert_allclose(got[hi:], plain[hi:] / 16, rtol=2e-6)
    assert rope.yarn_attention_factor(YARN) == pytest.approx(
        0.1 * math.log(16) + 1, rel=1e-12)
    # cos and sin carry the attention factor: every rotated pair's norm,
    # and so q.k, scales by it (q.k by its square)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, hd))
    pos = jnp.arange(5)[None] * 1000
    y = rope.apply_rope(x, pos, theta, YARN)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(y), axis=-1),
        1.2772588722239782 * np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-5)


def test_plain_rotary_is_unchanged():
    """A model with one rotary computes exactly what it did before YaRN
    existed (the yarn-less path adds no op)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 7, 2, 64))
    pos = jnp.arange(7)[None]
    f = rope.rope_freqs(64, 1e6)
    ang = pos.astype(jnp.float32)[..., None] * f
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    want = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)
    np.testing.assert_array_equal(np.asarray(rope.apply_rope(x, pos, 1e6)),
                                  np.asarray(want))
    a = get_config("qwen2-1.5b").attn
    assert a.rotary(True) == a.rotary(False) == (1e6, None)


# -- static per-layer windows -------------------------------------------------


def traced_window_calls(a, is_global, fn):
    """The kernel calls as the program made them before: the window of the
    layer scan's traced flag handed straight to the call, which then
    falls back to jnp."""
    return fn(attn_mod.layer_window(a, is_global))


def serve_logits(cfg, n=75, steps=3):
    """Chunked prefill (chunk 32), finalize (lookaheadkv, budget 48), then
    a few decode steps: every logit row the serving path computes."""
    key = jax.random.PRNGKey(7)
    params = tf.init_params(key, cfg)
    lkv = init_lookahead_params(jax.random.PRNGKey(8), cfg, params["layers"])
    prompt = jax.random.randint(jax.random.PRNGKey(9), (1, 96), 0,
                                cfg.vocab_size)
    state = tf.init_chunk_state(cfg, "lookaheadkv", 1, 128)
    nt = jnp.asarray(n, jnp.int32)
    out = []
    for s in range(0, n, 32):
        state, lg = tf.prefill_chunk(params, cfg, state,
                                     prompt[:, s:s + 32], nt,
                                     policy="lookaheadkv")
    out.append(lg)
    cache = tf.prefill_finalize(params, cfg, state, nt, policy="lookaheadkv",
                                evict=EvictionConfig(budget=48),
                                lkv_params=lkv, extra_slots=steps)
    tok = jnp.argmax(lg, -1)[:, None]
    for _ in range(steps):
        lg, cache = tf.decode_step(params, cfg, tok, cache)
        out.append(lg)
        tok = jnp.argmax(lg, -1)[:, None]
    return np.asarray(jnp.concatenate(out)), np.asarray(cache["attn"]["pos"])


@pytest.mark.parametrize("pallas", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("arch", ["gemma3-1b", "mellum2-12b"])
def test_kernel_calls_by_kind_match_the_traced_window(monkeypatch, arch,
                                                      pallas):
    """Chunk attention and lookahead scoring dispatched by layer kind (a
    static window in each branch of a cond on the scan's flag) give the
    logits and kept sets of the traced-window calls the program made
    before, on the jnp path and with the Pallas kernels (interpret
    mode), where only the new calls reach the kernels."""
    cfg = f32(get_smoke_config(arch))
    if arch == "gemma3-1b":  # two periods of its 5:1 pattern, plus one
        cfg = dataclasses.replace(cfg, num_layers=4, attn=dataclasses.replace(
            cfg.attn, global_every=3))
    if pallas:
        monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    got, kept = serve_logits(cfg)
    with monkeypatch.context() as m:
        m.setattr(attn_mod, "by_kind", traced_window_calls)
        want, want_kept = serve_logits(cfg)
    np.testing.assert_array_equal(kept, want_kept)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_windowed_calls_reach_the_kernel(monkeypatch):
    """With the Pallas dispatch on, every layer's chunk attention is a
    kernel call: the windowed layers' under ``chunk_attention_window``."""
    from repro.kernels import chunk_attention as ck

    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    cfg = get_smoke_config("mellum2-12b")
    params = jax.eval_shape(lambda k: tf.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    state = jax.eval_shape(
        lambda: tf.init_chunk_state(cfg, "lookaheadkv", 1, 64))
    windows = []
    orig = ck.chunk_attention_pallas

    def spy(*args, window=None, **kw):
        windows.append(window)
        return orig(*args, window=window, **kw)

    monkeypatch.setattr(ck, "chunk_attention_pallas", spy)
    jax.eval_shape(lambda p, s: tf.prefill_chunk(
        p, cfg, s, jnp.zeros((1, 32), jnp.int32), jnp.int32(40),
        policy="lookaheadkv"), params, state)
    assert set(windows) == {None, cfg.attn.sliding_window}


# -- the serving engine -------------------------------------------------------


def test_engine_serves_every_layer_on_the_kernels(monkeypatch):
    """Pallas forced (interpret mode): a model with windowed and full
    layers reports the fused Pallas scoring path, and ``moe.routed_rows``
    counts every prompt row's top-k once per layer when all experts are
    held."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    base = get_smoke_config("mellum2-12b")
    cfg = dataclasses.replace(
        base, num_layers=4, moe=dataclasses.replace(base.moe, experts_held=0))
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    lkv = init_lookahead_params(jax.random.PRNGKey(1), cfg, params["layers"])
    pool = KVBlockPool(cfg, block_size=16, pool_mb=2)
    eng = ContinuousEngine(params, cfg, ServingConfig(
        policy="lookaheadkv", evict=EvictionConfig(budget=32),
        chunking=ChunkingConfig(chunk=32, max_context=64), num_slots=2,
        max_new_tokens=3, kv_pool=pool), lkv_params=lkv)
    rng = np.random.default_rng(0)
    lens = (40, 70)
    eng.run([Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, n),
                     max_new_tokens=3) for i, n in enumerate(lens)])
    assert eng.metrics.get("serving_build").value()["score_path"] \
        == "pallas-fused"
    rows = eng.routed_rows()
    assert sorted(rows) == list(range(cfg.moe.num_experts))
    assert sum(rows.values()) == sum(lens) * cfg.num_layers * cfg.moe.top_k
