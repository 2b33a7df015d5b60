"""Mellum2 (JetBrains Mellum2-12B-A2.5B): a GQA decoder with sparse experts
on every layer, sliding-window and full-attention layers side by side, and
a rotary embedding per kind of layer.

- Attention: ``layer_types`` gives each layer's kind; a sliding layer's
  query at position i sees keys at positions j with i - j < window.
  ``head_dim`` is stated apart from hidden size over heads; no projection
  bias; no q/k norm (config.json has no key for one).
- Rotary (rotate-half): ``rope_parameters`` per kind.  ``default``: inverse
  frequencies theta^(-2i/hd).  ``yarn`` (arXiv:2309.00071, as Hugging Face
  transformers computes it): pair i of the default frequencies is kept
  below the ramp and divided by ``factor`` above it, the ramp running
  linearly from the pair that turns ``beta_fast`` times over
  ``original_max_position_embeddings`` positions (rounded down) to the one
  that turns ``beta_slow`` times (rounded up); cos and sin are multiplied by
  ``attention_factor``.
- FFN: a softmax router over ``num_experts_published`` experts, the top
  ``num_experts_per_tok`` gates renormalised (``norm_topk_prob``), no shared
  expert; SiLU-gated experts of width ``moe_intermediate_size``.  A chip of
  an expert-parallel deployment holds ``num_experts`` of them from
  ``first_expert`` and adds only their gated outputs; the others' part is
  left out here as in the program.
- Lookahead LoRA on the attention projections only.

Written from the equations; imports nothing of the program except its
``ModelConfig`` in ``model_config``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, reference

WIDE = 1 << 30  # a full-attention layer's window: wider than any sequence


def _kinds(c: dict) -> list:
    """True for each full-attention layer."""
    lt = c["layer_types"]
    assert len(lt) == int(c["num_hidden_layers"])
    assert set(lt) <= {"sliding_attention", "full_attention"}, set(lt)
    return [t == "full_attention" for t in lt]


def _rotary(part: dict) -> tuple:
    """(theta, yarn) of one ``rope_parameters`` entry; yarn is None or
    (factor, original length, beta_fast, beta_slow, attention factor)."""
    theta = float(part["rope_theta"])
    if part["rope_type"] == "default":
        return theta, None
    assert part["rope_type"] == "yarn", part["rope_type"]
    factor = float(part["factor"])
    af = part.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return theta, (factor, int(part["original_max_position_embeddings"]),
                   float(part.get("beta_fast", 32)),
                   float(part.get("beta_slow", 1)), float(af))


def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.common.config import (AttentionConfig, LookaheadConfig,
                                     ModelConfig, MoEConfig, YarnConfig)

    assert all(t == "sparse" for t in c["mlp_layer_types"])
    assert c["norm_topk_prob"] and not c["attention_bias"]
    full = _kinds(c)
    L = len(full)
    every = full.index(True) + 1 if any(full) else 0
    periodic = every and L % every == 0 and full == [
        i % every == every - 1 for i in range(L)]
    rp = c["rope_parameters"]
    (g_theta, g_yarn), (l_theta, l_yarn) = (
        _rotary(rp["full_attention"]), _rotary(rp["sliding_attention"]))

    def yarn(y):
        return None if y is None else YarnConfig(
            factor=y[0], original_max_position=y[1], beta_fast=y[2],
            beta_slow=y[3], attention_factor=y[4])

    lk = c["lookahead"]
    ff = int(c["moe_intermediate_size"])
    return ModelConfig(
        name=c["name"], arch_type="moe",
        num_layers=L, d_model=int(c["hidden_size"]), d_ff=ff,
        vocab_size=int(c["vocab_size"]),
        attn=AttentionConfig(
            num_heads=int(c["num_attention_heads"]),
            num_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]), qkv_bias=False,
            rope_theta=g_theta, rope_yarn=yarn(g_yarn),
            local_rope_theta=l_theta, local_rope_yarn=yarn(l_yarn),
            sliding_window=int(c["sliding_window"]) if not all(full) else 0,
            global_every=every if periodic else 0,
            global_layers=() if periodic else tuple(
                i for i, f in enumerate(full) if f)),
        moe=MoEConfig(num_experts=int(c["num_experts_published"]),
                      top_k=int(c["num_experts_per_tok"]), d_expert=ff,
                      experts_held=int(c["num_experts"]),
                      first_expert=int(c["first_expert"])),
        lookahead=LookaheadConfig(
            n_lookahead=int(lk["n_lookahead"]), lora_rank=int(lk["lora_rank"]),
            lora_alpha=float(lk["lora_alpha"]),
            pool_kernel=int(lk["pool_kernel"]),
            window_size=int(lk["window_size"]),
            lora_targets=("wq", "wk", "wv", "wo")),
        norm_eps=float(c["rms_norm_eps"]), act=c["hidden_act"],
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype=c["torch_dtype"], source=c["source"])


def draw(name, z, shape):
    """The router is a (d_model, experts) matrix: fan-in scale."""
    return z / np.sqrt(shape[-2]) if name == "router" else None


# -- the reference's pieces --------------------------------------------------


def _yarn_of(y):
    """A program ``YarnConfig`` as the plain tuple ``_rotary`` gives."""
    return None if y is None else (
        float(y.factor), int(y.original_max_position), float(y.beta_fast),
        float(y.beta_slow), float(y.attention_factor))


def spec_of(cfg, budget: int) -> reference.Spec:
    a, m = cfg.attn, cfg.moe
    full = [bool(f) for f in _full_layers(cfg)]
    return reference.spec(cfg, budget, (
        (float(a.rope_theta), _yarn_of(a.rope_yarn)),
        (float(a.local_rope_theta or a.rope_theta),
         _yarn_of(a.local_rope_yarn)),
        int(a.sliding_window), tuple(full),
        int(m.top_k), int(m.first_expert), int(m.held)))


def _full_layers(cfg):
    a = cfg.attn
    L = cfg.num_layers
    if a.global_layers:
        return [i in a.global_layers for i in range(L)]
    if a.global_every:
        return [i % a.global_every == a.global_every - 1 for i in range(L)]
    return [True] * L


def layers(params, lkv, spec):
    """One group of every layer, each carrying its window and its kind
    (True: full attention, with the full layers' rotary)."""
    win, full = spec.model[2], spec.model[3]
    return [{"p": params["layers"], "lora": lkv["lora"],
             "window": jnp.asarray([WIDE if f else win for f in full],
                                   jnp.int32),
             "full": jnp.asarray(full)}]


def window(x, spec):
    return x["window"]


def inv_freq(hd: int, theta: float, yarn) -> tuple:
    """(inverse frequencies (hd/2,) f32, cos/sin scale) of one rotary."""
    pos_freqs = theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    extra = 1.0 / pos_freqs
    if yarn is None:
        return extra, 1.0
    factor, orig, fast, slow, af = yarn

    def pair(rot):
        return hd * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair(fast)), 0)
    high = min(math.ceil(pair(slow)), hd - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    inter = 1.0 / (factor * pos_freqs)
    return inter * (1.0 - keep) + extra * keep, af


def rope(x, pos, rot):
    """Rotary embedding, rotate-half convention.  x: (S, H, hd)."""
    theta, yarn = rot
    hd = x.shape[-1]
    inv, scale = inv_freq(hd, theta, yarn)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _rope_kind(x, pos, spec, is_full):
    g, loc = spec.model[0], spec.model[1]
    return jnp.where(is_full, rope(x, pos, g), rope(x, pos, loc))


def qkv(x, u, pos, spec, lora, quant):
    p, la = x["p"]["attn"], None if lora is None else lora.get("attn")
    S, hd, sc = u.shape[0], spec.head_dim, spec.lora_scale
    q = reference.linear(u, p, "wq", None, la, sc, quant)
    k = reference.linear(u, p, "wk", None, la, sc, quant)
    v = reference.linear(u, p, "wv", None, la, sc, quant)
    return (_rope_kind(q.reshape(S, spec.heads, hd), pos, spec, x["full"]),
            _rope_kind(k.reshape(S, spec.kv_heads, hd), pos, spec, x["full"]),
            v.reshape(S, spec.kv_heads, hd))


def out(x, att, spec, lora, quant):
    la = None if lora is None else lora.get("attn")
    return reference.linear(att, x["p"]["attn"], "wo", None, la,
                            spec.lora_scale, quant)


def ffn(x, u, spec, lora, quant):
    """Route each row over all the router's experts, keep the top k with
    their gates renormalised, and sum the gated outputs of the experts
    held here, one expert at a time (float32 weights of this layer only)."""
    p = x["p"]["moe"]
    top_k, first, held = spec.model[4], spec.model[5], spec.model[6]
    probs = jax.nn.softmax(
        reference.mm(u, p["router"].astype(jnp.float32), quant), -1)
    g, idx = jax.lax.top_k(probs, top_k)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(u.shape[0])[:, None], idx].set(g / g.sum(-1, keepdims=True))
    gate = gate[:, first:first + held]  # (rows, held)

    def expert(acc, e):
        wg, wu, wd, ge = e
        h = jax.nn.silu(reference.mm(u, wg.astype(jnp.float32), quant)) \
            * reference.mm(u, wu.astype(jnp.float32), quant)
        return acc + reference.mm(h, wd.astype(jnp.float32), quant) \
            * ge[:, None], None

    w = p["experts"]
    acc, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                          (w["w_gate"], w["w_up"], w["w_down"], gate.T))
    return acc


# -- the counts --------------------------------------------------------------


@dataclass(frozen=True)
class Widths:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    top_k: int
    router: int  # the router's outputs (all the experts)
    held: int  # experts held on this chip
    expert_ff: int
    window: int
    n_full: int  # full-attention layers
    vocab: int

    def kinds(self):
        """(layers, window) of the sliding and of the full layers."""
        return ((self.layers - self.n_full, self.window), (self.n_full, None))

    def layer_linear_flops(self) -> float:
        """One token through one layer's projections, its router, and its
        expected share of experts here: top_k * held / router of them."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        proj = 2 * self.d * q + 2 * self.d * kv
        experts = self.top_k * self.held / self.router
        return 2 * (proj + self.d * self.router
                    + experts * 3 * self.d * self.expert_ff)

    def prefill_chunk_flops(self, start: int, rows: int) -> float:
        return sum(
            n * (rows * self.layer_linear_flops()
                 + flops.attn_flops(self.heads, self.head_dim, 1)
                 * flops.visible_keys(start, rows, w))
            for n, w in self.kinds()) + 2 * self.d * self.vocab

    def decode_token_flops(self, keys: float) -> float:
        return sum(
            n * (self.layer_linear_flops()
                 + flops.attn_flops(self.heads, self.head_dim,
                                    keys if w is None else min(keys, w)))
            for n, w in self.kinds()) + 2 * self.d * self.vocab

    def chunk_attn_call(self, start: int, rows: int, chips: int = 1):
        return tuple((n, *flops.chunk_attn_call(
            self.heads, self.kv_heads, self.head_dim, start, rows, chips, w))
            for n, w in self.kinds())

    def window_attn_call(self, start: int, rows: int, chips: int = 1):
        """The sliding layers' chunk-attention calls alone: each query over
        the keys its window shows."""
        return self.chunk_attn_call(start, rows, chips)[:1]

    def paged_decode_call(self, slots: int, keys: float, chips: int = 1):
        return tuple((n, *flops.paged_decode_call(
            self.heads, self.kv_heads, self.head_dim, slots,
            keys if w is None else min(keys, w), chips))
            for n, w in self.kinds())

    def lookahead_score_call(self, n_prompt: int, n_obs: int,
                             chips: int = 1):
        return tuple((n, *flops.lookahead_score_call(
            self.heads, self.kv_heads, self.head_dim,
            n_prompt if w is None else min(n_prompt, w), n_obs, chips))
            for n, w in self.kinds())


def widths(c: dict) -> Widths:
    full = _kinds(c)
    return Widths(layers=len(full), d=int(c["hidden_size"]),
                  heads=int(c["num_attention_heads"]),
                  kv_heads=int(c["num_key_value_heads"]),
                  head_dim=int(c["head_dim"]),
                  top_k=int(c["num_experts_per_tok"]),
                  router=int(c["num_experts_published"]),
                  held=int(c["num_experts"]),
                  expert_ff=int(c["moe_intermediate_size"]),
                  window=int(c["sliding_window"]), n_full=sum(full),
                  vocab=int(c["vocab_size"]))
