"""A CPU test architecture, not a real model: what an architecture other
than Qwen2 brings to the harness as a file of its own, with mechanisms the
program already serves.

- FFN: top-k softmax mixture of experts (``num_experts``,
  ``num_experts_per_tok``, width ``moe_intermediate_size``), the top-k gates
  renormalised to sum to 1, no shared expert; the program runs it by dense
  dispatch, the reference routes over all the experts.
- Attention: every ``global_every``-th layer global, the others see the
  last ``sliding_window`` keys; ``head_dim`` stated apart from hidden size
  over heads; no projection bias; one rotary embedding.
- Lookahead LoRA on the attention projections only (the experts' stacked
  weights take none in the program).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, reference

WIDE = 1 << 30  # a global layer's window: wider than any sequence


def model_config(c: dict):
    from repro.common.config import (AttentionConfig, LookaheadConfig,
                                     ModelConfig, MoEConfig)

    lk = c["lookahead"]
    ff = int(c["moe_intermediate_size"])
    return ModelConfig(
        name=c["name"], arch_type="moe",
        num_layers=int(c["num_hidden_layers"]), d_model=int(c["hidden_size"]),
        d_ff=ff, vocab_size=int(c["vocab_size"]),
        attn=AttentionConfig(
            num_heads=int(c["num_attention_heads"]),
            num_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]), qkv_bias=False,
            rope_theta=float(c["rope_theta"]),
            sliding_window=int(c["sliding_window"]),
            global_every=int(c["global_every"])),
        moe=MoEConfig(num_experts=int(c["num_experts"]),
                      top_k=int(c["num_experts_per_tok"]), d_expert=ff),
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


def spec_of(cfg, budget: int) -> reference.Spec:
    a = cfg.attn
    return reference.spec(cfg, budget, (float(a.rope_theta), cfg.moe.top_k,
                                        a.sliding_window, a.global_every))


def layers(params, lkv, spec):
    _, _, win, every = spec.model
    n = params["layers"]["ln1"].shape[0]
    wins = [WIDE if i % every == every - 1 else win for i in range(n)]
    return [{"p": params["layers"], "lora": lkv["lora"],
             "window": jnp.asarray(wins, jnp.int32)}]


def window(x, spec):
    return x["window"]


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def qkv(x, u, pos, spec, lora, quant):
    p, la = x["p"]["attn"], None if lora is None else lora.get("attn")
    S, hd, sc = u.shape[0], spec.head_dim, spec.lora_scale
    q = reference.linear(u, p, "wq", None, la, sc, quant)
    k = reference.linear(u, p, "wk", None, la, sc, quant)
    v = reference.linear(u, p, "wv", None, la, sc, quant)
    theta = spec.model[0]
    return (_rope(q.reshape(S, spec.heads, hd), pos, theta),
            _rope(k.reshape(S, spec.kv_heads, hd), pos, theta),
            v.reshape(S, spec.kv_heads, hd))


def out(x, att, spec, lora, quant):
    la = None if lora is None else lora.get("attn")
    return reference.linear(att, x["p"]["attn"], "wo", None, la,
                            spec.lora_scale, quant)


def ffn(x, u, spec, lora, quant):
    """Route each row over all the experts, keep the top k, and sum their
    outputs weighted by the renormalised gates."""
    p, top_k = x["p"]["moe"], spec.model[1]
    probs = jax.nn.softmax(
        reference.mm(u, p["router"].astype(jnp.float32), quant), -1)
    g, idx = jax.lax.top_k(probs, top_k)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(u.shape[0])[:, None], idx].set(g / g.sum(-1, keepdims=True))
    e = {k: w.astype(jnp.float32) for k, w in p["experts"].items()}
    h = jax.nn.silu(reference.mm(u, e["w_gate"], quant)) \
        * reference.mm(u, e["w_up"], quant)  # (experts, rows, width)
    y = reference.mm(h, e["w_down"], quant)  # (experts, rows, d_model)
    return jnp.einsum("esd,se->sd", y, gate, precision=reference.HI)


# -- the counts --------------------------------------------------------------


@dataclass(frozen=True)
class Widths:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    top_k: int
    experts: int
    expert_ff: int
    window: int
    global_every: int
    vocab: int

    def kinds(self):
        """(layers, window) of the sliding and of the global layers."""
        n_global = self.layers // self.global_every
        return ((self.layers - n_global, self.window), (n_global, None))

    def layer_linear_flops(self) -> int:
        """One token through one layer's projections, router and its top-k
        experts."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        proj = 2 * self.d * q + 2 * self.d * kv
        return 2 * (proj + self.d * self.experts
                    + self.top_k * 3 * self.d * self.expert_ff)

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
    return Widths(layers=int(c["num_hidden_layers"]),
                  d=int(c["hidden_size"]),
                  heads=int(c["num_attention_heads"]),
                  kv_heads=int(c["num_key_value_heads"]),
                  head_dim=int(c["head_dim"]),
                  top_k=int(c["num_experts_per_tok"]),
                  experts=int(c["num_experts"]),
                  expert_ff=int(c["moe_intermediate_size"]),
                  window=int(c["sliding_window"]),
                  global_every=int(c["global_every"]),
                  vocab=int(c["vocab_size"]))
