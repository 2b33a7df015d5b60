"""Plain float32 reference of what the served path computes for one
request, written from the model's and the method's equations and
importing nothing of the program.

This file holds the method's part; an architecture module
(``chipbench/arch/<arch>.py``) gives the model's: each layer's q/k/v
projections with their rotary embedding, its output projection, its FFN,
its attention window, and how the layers are grouped.  For a prompt of n
tokens and the tokens t_0..t_{m-1} the program served:

1. the prompt runs through every layer with causal attention; the last
   row's logits are the reference for t_0;
2. the 32 lookahead rows (learned embeddings, LoRA on the linears the
   lookahead modules hold) run after the prompt, at positions n..,
   attending to the prompt and causally to each other; their softmax mass
   on each prompt key, averaged over the rows and over the query heads of
   a kv group, max-pooled over 7 keys, ranks the prompt keys, and the
   ``budget`` highest of each (layer, kv head) are kept (LookaheadKV,
   arXiv:2603.10899, with SnapKV's pooling);
3. the served tokens t_0..t_{m-2}, at positions n.., attend to the kept
   prompt keys of their (layer, kv head) and causally to each other; row j
   gives the reference logits for t_{j+1}.

On a layer with a window w a query at position i sees only keys at
positions j with i - j < w, in all three phases.  Everything is float32
with ``Precision.HIGHEST`` matrix products, layer by layer under one
``lax.scan`` per group of layers, prompt attention in blocks of query
rows.  With ``quant=True`` every matrix product takes its operands through
float8 (e4m3) first: the lower-precision control, which must fail.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
NEG = -1e30
QBLOCK = 512  # prompt query rows per attention block
F8_MAX = 448.0  # largest finite float8_e4m3fn


class Spec(NamedTuple):
    """The static values ``forward`` runs with: the attention geometry, the
    method's settings, and ``model``, the architecture module's own."""

    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    budget: int
    pool: int
    lora_scale: float
    vocab: int
    tied: bool
    model: tuple = ()


def spec(cfg, budget: int, model: tuple = ()) -> Spec:
    """The ``Spec`` of the program's ``ModelConfig`` at ``budget`` kept
    rows per (layer, kv head)."""
    a, lk = cfg.attn, cfg.lookahead
    return Spec(heads=a.num_heads, kv_heads=a.num_kv_heads,
                head_dim=a.head_dim, eps=float(cfg.norm_eps),
                budget=int(budget), pool=int(lk.pool_kernel),
                lora_scale=float(lk.lora_alpha) / lk.lora_rank,
                vocab=int(cfg.vocab_size), tied=bool(cfg.tie_embeddings),
                model=model)


def _fq(x, axis):
    """Fake-quantize to float8 e4m3 with one scale per slice along
    ``axis``'s complement (per row of an activation, per column of a
    weight)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, quant):
    """``x @ w`` at ``Precision.HIGHEST``; through float8 with ``quant``."""
    if quant:
        x, w = _fq(x, -1), _fq(w, -2)
    return jnp.matmul(x, w, precision=HI)


def linear(x, p, wname, bname, lora, scale, quant):
    """``x @ p[wname]`` (+ ``p[bname]``), plus the lookahead LoRA of
    ``wname`` where ``lora`` (a layer's LoRA tree) holds one."""
    y = mm(x, p[wname].astype(jnp.float32), quant)
    if bname is not None:
        y = y + p[bname].astype(jnp.float32)
    if lora is not None and wname in lora:
        a, b = lora[wname]["a"], lora[wname]["b"]
        y = y + mm(mm(x, a, quant), b, quant) * scale
    return y


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


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


def _near(qpos, kpos, win):
    """(queries, keys) bool: the key lies inside the query's window."""
    return qpos[:, None] - kpos[None, :] < win


@partial(jax.jit, static_argnames=("spec", "arch", "quant"))
def forward(params, lkv, prompt, n, gen, *, spec, arch, quant=False):
    """Reference logits and kept sets for one request.

    prompt (Np,) int32 padded past ``n``; gen (M,) int32 the served tokens
    (padded).  ``spec`` is the ``Spec`` that ``arch.spec_of`` gives;
    ``arch`` the architecture module.  Returns (logits (M, vocab): row j
    the reference for served token j; kept (L, KV, Np) bool)."""
    H, KV, eps, budget = spec.heads, spec.kv_heads, spec.eps, spec.budget
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
        p = x["p"]
        ln1 = p["ln1"].astype(jnp.float32)
        ln2 = p["ln2"].astype(jnp.float32)
        win = arch.window(x, spec)
        # -- prompt rows: causal attention, in blocks of query rows
        qp, kp, vp = arch.qkv(x, _rms(hp, ln1, eps), ppos, spec, None, quant)

        def block(i):
            rows = i * QBLOCK + jnp.arange(QBLOCK)
            qb = jax.lax.dynamic_slice_in_dim(qp, i * QBLOCK, QBLOCK, 0)
            ok = (ppos[None, :] <= rows[:, None]) & valid[None, :]
            if win is not None:
                ok = ok & _near(rows, ppos, win)
            return _attend(qb, kp, vp, ok[:, None, :], quant)[0]

        att = jax.lax.map(block, jnp.arange(Np // QBLOCK)).reshape(Np, -1)
        hp = hp + arch.out(x, att, spec, None, quant)
        hp = hp + arch.ffn(x, _rms(hp, ln2, eps), spec, None, quant)
        # -- lookahead rows: LoRA on, prompt keys + causal lookahead keys
        lo = x["lora"]
        qo, ko, vo = arch.qkv(x, _rms(ho, ln1, eps), opos, spec, lo, quant)
        ok = jnp.concatenate(
            [jnp.broadcast_to(valid[None], (O, Np)),
             jnp.arange(O)[None, :] <= jnp.arange(O)[:, None]], 1)
        if win is not None:
            ok = ok & _near(opos, jnp.concatenate([ppos, opos]), win)
        att, pr = _attend(qo, jnp.concatenate([kp, ko]),
                          jnp.concatenate([vp, vo]), ok[:, None, :], quant)
        ho = ho + arch.out(x, att, spec, lo, quant)
        ho = ho + arch.ffn(x, _rms(ho, ln2, eps), spec, lo, quant)
        # -- eviction: mean over rows, mean over each kv group's heads,
        # max-pool over keys, top-``budget`` per kv head
        sc = pr[:, :, :Np].mean(1).reshape(KV, H // KV, Np).mean(1)
        sc = jnp.where(valid[None], sc, -jnp.inf)
        sc = jnp.where(valid[None], _maxpool(sc, spec.pool), NEG)
        _, idx = jax.lax.top_k(sc, cap)
        kept = jnp.zeros((KV, Np), bool).at[
            jnp.arange(KV)[:, None], idx].set(True) & valid[None]
        # -- served tokens: kept prompt keys of their kv head + causal
        qg, kg, vg = arch.qkv(x, _rms(hg, ln1, eps), gpos, spec, None, quant)
        okp = jnp.broadcast_to(kept[None], (M, KV, Np))
        okg = jnp.broadcast_to(
            (jnp.arange(M)[None, :] <= jnp.arange(M)[:, None])[:, None, :],
            (M, KV, M))
        ok = jnp.concatenate([okp, okg], -1)
        if win is not None:
            ok = ok & _near(gpos, jnp.concatenate([ppos, gpos]), win)[:, None]
        att, _ = _attend(qg, jnp.concatenate([kp, kg]),
                         jnp.concatenate([vp, vg]), ok, quant)
        hg = hg + arch.out(x, att, spec, None, quant)
        hg = hg + arch.ffn(x, _rms(hg, ln2, eps), spec, None, quant)
        return (hp, ho, hg), kept

    carry, kept = (hp, ho, hg), []
    for xs in arch.layers(params, lkv, spec):
        carry, k = jax.lax.scan(layer, carry, xs)
        kept.append(k)
    hp, _, hg = carry
    kept = kept[0] if len(kept) == 1 else jnp.concatenate(kept)
    fn = params["final_norm"].astype(jnp.float32)
    head = (emb.T if spec.tied else params["lm_head"].astype(jnp.float32))
    last = jax.lax.dynamic_slice_in_dim(hp, n - 1, 1, 0)
    rows = jnp.concatenate([last, hg[:-1]])  # row j predicts served token j
    logits = mm(_rms(rows, fn, eps), head, quant)[:, :spec.vocab]
    return logits, kept


@jax.jit
def gaps(logits, tokens):
    """Per row: how far the logit of ``tokens[j]`` lies below row j's best."""
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, tokens[:, None], -1)[:, 0]
    return best - got
