"""Qwen2 (arXiv:2407.10671): a dense GQA decoder with q/k/v projection
biases, one rotary embedding (rotate-half, base ``rope_theta``), a SiLU
gated MLP and RMSNorm; every layer attends to its whole causal prefix.

An architecture module gives the harness what is the model's and not the
method's (``chipbench/README.md``, "An architecture"): the program's
``ModelConfig`` for a configuration file, the reference's per-layer
pieces, and the analytic counts.  A configuration file without an
``arch`` key is this one.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from chipbench import flops, reference


def head_dim(c: dict) -> int:
    """``head_dim`` where the file gives it, else hidden size over heads
    (Qwen2's config.json has none)."""
    if "head_dim" in c:
        return int(c["head_dim"])
    return int(c["hidden_size"]) // int(c["num_attention_heads"])


def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.common.config import (AttentionConfig, LookaheadConfig,
                                     ModelConfig)

    lk = c["lookahead"]
    return ModelConfig(
        name=c["name"], arch_type="dense",
        num_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]), d_ff=int(c["intermediate_size"]),
        vocab_size=int(c["vocab_size"]),
        attn=AttentionConfig(
            num_heads=int(c["num_attention_heads"]),
            num_kv_heads=int(c["num_key_value_heads"]),
            head_dim=head_dim(c), qkv_bias=True,
            rope_theta=float(c["rope_theta"])),
        lookahead=LookaheadConfig(
            n_lookahead=int(lk["n_lookahead"]), lora_rank=int(lk["lora_rank"]),
            lora_alpha=float(lk["lora_alpha"]),
            pool_kernel=int(lk["pool_kernel"]),
            window_size=int(lk["window_size"])),
        norm_eps=float(c["rms_norm_eps"]), act=c["hidden_act"],
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype=c["torch_dtype"], source=c["source"])


# -- the reference's pieces --------------------------------------------------


def spec_of(cfg, budget: int) -> reference.Spec:
    return reference.spec(cfg, budget, (float(cfg.attn.rope_theta),))


def layers(params, lkv, spec):
    """One group: every layer alike."""
    return [{"p": params["layers"], "lora": lkv["lora"]}]


def window(x, spec):
    return None


def _part(lora, name):
    """A layer's LoRA for one block, or None where LoRA is off."""
    return None if lora is None else lora.get(name)


def rope(x, pos, theta):
    """Rotary embedding, rotate-half convention.  x: (S, H, hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def qkv(x, u, pos, spec, lora, quant):
    p, la = x["p"]["attn"], _part(lora, "attn")
    H, KV, hd, (theta,) = spec.heads, spec.kv_heads, spec.head_dim, spec.model
    sc, S = spec.lora_scale, u.shape[0]
    q = reference.linear(u, p, "wq", "bq", la, sc, quant).reshape(S, H, hd)
    k = reference.linear(u, p, "wk", "bk", la, sc, quant).reshape(S, KV, hd)
    v = reference.linear(u, p, "wv", "bv", la, sc, quant).reshape(S, KV, hd)
    return rope(q, pos, theta), rope(k, pos, theta), v


def out(x, att, spec, lora, quant):
    return reference.linear(att, x["p"]["attn"], "wo", None,
                            _part(lora, "attn"), spec.lora_scale, quant)


def ffn(x, u, spec, lora, quant):
    p, lm, sc = x["p"]["mlp"], _part(lora, "mlp"), spec.lora_scale
    g = reference.linear(u, p, "w_gate", None, lm, sc, quant)
    up = reference.linear(u, p, "w_up", None, lm, sc, quant)
    return reference.linear(jax.nn.silu(g) * up, p, "w_down", None, lm, sc,
                            quant)


# -- the counts --------------------------------------------------------------


@dataclass(frozen=True)
class Widths:
    layers: int
    d: int
    ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    def layer_linear_flops(self) -> int:
        """One token through one layer's projections and MLP (with
        biases)."""
        proj = (self.d * self.q_dim + 2 * self.d * self.kv_dim
                + self.q_dim * self.d)
        mlp = 3 * self.d * self.ff
        return 2 * (proj + mlp) + (self.q_dim + 2 * self.kv_dim)

    def unembed_flops(self) -> int:
        return 2 * self.d * self.vocab

    def prefill_chunk_flops(self, start: int, rows: int) -> float:
        """Prompt rows [start, start + rows) through the model, with the
        logits of the chunk's last row."""
        keys = flops.visible_keys(start, rows)  # summed causal prefixes
        return (self.layers * (rows * self.layer_linear_flops()
                               + flops.attn_flops(self.heads, self.head_dim,
                                                  1) * keys)
                + self.unembed_flops())

    def decode_token_flops(self, keys: float) -> float:
        """One generated token attending to ``keys`` cached rows per
        layer."""
        return (self.layers * (self.layer_linear_flops()
                               + flops.attn_flops(self.heads, self.head_dim,
                                                  keys))
                + self.unembed_flops())

    def chunk_attn_call(self, start: int, rows: int, chips: int = 1):
        return ((self.layers, *flops.chunk_attn_call(
            self.heads, self.kv_heads, self.head_dim, start, rows, chips)),)

    def paged_decode_call(self, slots: int, keys: float, chips: int = 1):
        return ((self.layers, *flops.paged_decode_call(
            self.heads, self.kv_heads, self.head_dim, slots, keys, chips)),)

    def lookahead_score_call(self, n_prompt: int, n_obs: int,
                             chips: int = 1):
        return ((self.layers, *flops.lookahead_score_call(
            self.heads, self.kv_heads, self.head_dim, n_prompt, n_obs,
            chips)),)


def widths(c: dict) -> Widths:
    return Widths(layers=int(c["num_hidden_layers"]),
                  d=int(c["hidden_size"]), ff=int(c["intermediate_size"]),
                  heads=int(c["num_attention_heads"]),
                  kv_heads=int(c["num_key_value_heads"]),
                  head_dim=head_dim(c), vocab=int(c["vocab_size"]))
