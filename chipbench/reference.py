"""Plain float32 reference of what the served path computes for one
request, written from the model's and the method's equations and
importing nothing of the program.

For a prompt of n tokens and the tokens t_0..t_{m-1} the program served:

1. the prompt runs through every layer with full causal attention; the
   last row's logits are the reference for t_0;
2. the 32 lookahead rows (learned embeddings, LoRA on every linear) run
   after the prompt, at positions n.., attending to the prompt and
   causally to each other; their softmax mass on each prompt key, averaged
   over the rows and over the query heads of a kv group, max-pooled over
   7 keys, ranks the prompt keys, and the ``budget`` highest of each
   (layer, kv head) are kept (LookaheadKV, arXiv:2603.10899, with SnapKV's
   pooling);
3. the served tokens t_0..t_{m-2}, at positions n.., attend to the kept
   prompt keys of their (layer, kv head) and causally to each other; row j
   gives the reference logits for t_{j+1}.

Everything is float32 with ``Precision.HIGHEST`` matrix products, layer by
layer under one ``lax.scan``, prompt attention in blocks of query rows.
With ``quant=True`` every matrix product takes its operands through
float8 (e4m3) first: the lower-precision control, which must fail.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
NEG = -1e30
QBLOCK = 512  # prompt query rows per attention block
F8_MAX = 448.0  # largest finite float8_e4m3fn


def _fq(x, axis):
    """Fake-quantize to float8 e4m3 with one scale per slice along
    ``axis``'s complement (per row of an activation, per column of a
    weight)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    if quant:
        x, w = _fq(x, -1), _fq(w, -2)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, pos, theta):
    """Rotary embedding, rotate-half convention.  x: (S, H, hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _linear(x, p, wname, bname, lora, scale, quant):
    y = _mm(x, p[wname].astype(jnp.float32), quant)
    if bname is not None:
        y = y + p[bname].astype(jnp.float32)
    if lora is not None and wname in lora:
        a, b = lora[wname]["a"], lora[wname]["b"]
        y = y + _mm(_mm(x, a, quant), b, quant) * scale
    return y


def _qkv(x, p, pos, dims, lora, scale, quant):
    H, KV, hd, theta = dims
    S = x.shape[0]
    q = _linear(x, p, "wq", "bq", lora, scale, quant).reshape(S, H, hd)
    k = _linear(x, p, "wk", "bk", lora, scale, quant).reshape(S, KV, hd)
    v = _linear(x, p, "wv", "bv", lora, scale, quant).reshape(S, KV, hd)
    return _rope(q, pos, theta), _rope(k, pos, theta), v


def _mlp(x, p, lora, scale, quant):
    g = _linear(x, p, "w_gate", None, lora, scale, quant)
    u = _linear(x, p, "w_up", None, lora, scale, quant)
    return _linear(jax.nn.silu(g) * u, p, "w_down", None, lora, scale, quant)


def _attend(q, k, v, ok, quant):
    """q (S, H, hd); k, v (T, KV, hd); ok (S or 1, KV or 1, T) visibility.
    Returns (out (S, H*hd), probs (H, S, T))."""
    S, H, hd = q.shape
    KV = k.shape[1]
    g = H // KV
    kx = jnp.repeat(k, g, axis=1)  # query head h reads kv head h // g
    vx = jnp.repeat(v, g, axis=1)
    if quant:
        q, kx, vx = _fq(q, -1), _fq(kx, -1), _fq(vx, -1)
    s = jnp.einsum("shd,thd->hst", q, kx, precision=HI) / jnp.sqrt(
        jnp.float32(hd))
    okh = jnp.repeat(jnp.moveaxis(ok, 1, 0), g, axis=0) if ok.shape[1] > 1 \
        else jnp.moveaxis(ok, 1, 0)
    s = jnp.where(okh, s, NEG)
    pr = jax.nn.softmax(s, axis=-1)
    pv = pr if not quant else _fq(pr, -1)
    o = jnp.einsum("hst,thd->shd", pv, vx, precision=HI)
    return o.reshape(S, H * hd), pr


def _maxpool(x, kernel):
    pad = kernel // 2
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(pad, pad)],
                 constant_values=-jnp.inf)
    return jnp.max(jnp.stack([xp[..., i:i + x.shape[-1]]
                              for i in range(kernel)]), 0)


@partial(jax.jit, static_argnames=("spec", "quant"))
def forward(params, lkv, prompt, n, gen, *, spec, quant=False):
    """Reference logits and kept sets for one request.

    prompt (Np,) int32 padded past ``n``; gen (M,) int32 the served tokens
    (padded).  ``spec`` is the static tuple (H, KV, hd, theta, eps, budget,
    pool_kernel, lora_scale, vocab, tied).  Returns (logits (M, vocab):
    row j the reference for served token j; kept (L, KV, Np) bool)."""
    H, KV, hd, theta, eps, budget, pool, lscale, vocab, tied = spec
    dims = (H, KV, hd, theta)
    Np, M = prompt.shape[0], gen.shape[0]
    emb = params["embed"].astype(jnp.float32)
    hp = emb[prompt]
    hg = emb[gen]
    ho = lkv["emb"].astype(jnp.float32)
    O = ho.shape[0]
    ppos = jnp.arange(Np)
    opos = n + jnp.arange(O)
    gpos = n + jnp.arange(M)
    valid = ppos < n  # real prompt keys
    cap = min(budget, Np)

    def layer(carry, x):
        hp, ho, hg = carry
        p, lo = x["p"], x["lora"]
        pa, pm = p["attn"], p["mlp"]
        la, lm = lo.get("attn"), lo.get("mlp")
        ln1 = p["ln1"].astype(jnp.float32)
        ln2 = p["ln2"].astype(jnp.float32)
        # -- prompt rows: full causal attention, in blocks of query rows
        qp, kp, vp = _qkv(_rms(hp, ln1, eps), pa, ppos, dims, None, 0.0,
                          quant)

        def block(i):
            rows = i * QBLOCK + jnp.arange(QBLOCK)
            qb = jax.lax.dynamic_slice_in_dim(qp, i * QBLOCK, QBLOCK, 0)
            ok = (ppos[None, :] <= rows[:, None]) & valid[None, :]
            return _attend(qb, kp, vp, ok[:, None, :], quant)[0]

        att = jax.lax.map(block, jnp.arange(Np // QBLOCK)).reshape(Np, -1)
        hp = hp + _mm(att, pa["wo"].astype(jnp.float32), quant)
        hp = hp + _mlp(_rms(hp, ln2, eps), pm, None, 0.0, quant)
        # -- lookahead rows: LoRA on, prompt keys + causal lookahead keys
        qo, ko, vo = _qkv(_rms(ho, ln1, eps), pa, opos, dims, la, lscale,
                          quant)
        ok = jnp.concatenate(
            [jnp.broadcast_to(valid[None], (O, Np)),
             jnp.arange(O)[None, :] <= jnp.arange(O)[:, None]], 1)
        att, pr = _attend(qo, jnp.concatenate([kp, ko]),
                          jnp.concatenate([vp, vo]), ok[:, None, :], quant)
        ho = ho + _linear(att, pa, "wo", None, la, lscale, quant)
        ho = ho + _mlp(_rms(ho, ln2, eps), pm, lm, lscale, quant)
        # -- eviction: mean over rows, mean over each kv group's heads,
        # max-pool over keys, top-``budget`` per kv head
        sc = pr[:, :, :Np].mean(1).reshape(KV, H // KV, Np).mean(1)
        sc = jnp.where(valid[None], sc, -jnp.inf)
        sc = jnp.where(valid[None], _maxpool(sc, pool), NEG)
        _, idx = jax.lax.top_k(sc, cap)
        kept = jnp.zeros((KV, Np), bool).at[
            jnp.arange(KV)[:, None], idx].set(True) & valid[None]
        # -- served tokens: kept prompt keys of their kv head + causal
        qg, kg, vg = _qkv(_rms(hg, ln1, eps), pa, gpos, dims, None, 0.0,
                          quant)
        okp = jnp.broadcast_to(kept[None], (M, KV, Np))
        okg = jnp.broadcast_to(
            (jnp.arange(M)[None, :] <= jnp.arange(M)[:, None])[:, None, :],
            (M, KV, M))
        att, _ = _attend(qg, jnp.concatenate([kp, kg]),
                         jnp.concatenate([vp, vg]),
                         jnp.concatenate([okp, okg], -1), quant)
        hg = hg + _mm(att, pa["wo"].astype(jnp.float32), quant)
        hg = hg + _mlp(_rms(hg, ln2, eps), pm, None, 0.0, quant)
        return (hp, ho, hg), kept

    xs = {"p": params["layers"], "lora": lkv["lora"]}
    (hp, _, hg), kept = jax.lax.scan(layer, (hp, ho, hg), xs)
    fn = params["final_norm"].astype(jnp.float32)
    head = (emb.T if tied else params["lm_head"].astype(jnp.float32))
    last = jax.lax.dynamic_slice_in_dim(hp, n - 1, 1, 0)
    rows = jnp.concatenate([last, hg[:-1]])  # row j predicts served token j
    logits = _mm(_rms(rows, fn, eps), head, quant)[:, :vocab]
    return logits, kept


def spec_of(cfg, budget: int) -> tuple:
    a, lk = cfg.attn, cfg.lookahead
    return (a.num_heads, a.num_kv_heads, a.head_dim, float(a.rope_theta),
            float(cfg.norm_eps), int(budget), int(lk.pool_kernel),
            float(lk.lora_alpha) / lk.lora_rank, int(cfg.vocab_size),
            bool(cfg.tie_embeddings))


@jax.jit
def gaps(logits, tokens):
    """Per row: how far the logit of ``tokens[j]`` lies below row j's best."""
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, tokens[:, None], -1)[:, 0]
    return best - got
