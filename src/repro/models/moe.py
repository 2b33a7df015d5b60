"""Fine-grained mixture-of-experts FFN (DeepSeek-MoE, Phi-3.5-MoE).

Shared experts always run; routed experts are selected per token (top-k
softmax gating).  Two dispatch strategies:

* ``apply``  — *dense* dispatch: every expert runs over every token, the
  gate combine zeroes non-selected outputs.  Token axis is processed in
  chunks (``lax.map`` over the sequence) so the (chunk, E_local, Fe)
  intermediate stays VMEM/HBM-bounded.  Shape-static, trivially
  expert-parallel (experts shard on "model"; the combine contracts locally,
  no all-to-all), and exactly differentiable — this is the paper-faithful
  baseline the dry-run lowers.  Cost: E/K× extra FFN FLOPs, which the
  roofline's MODEL_FLOPS/HLO_FLOPs ratio surfaces honestly (§Perf hillclimbs
  it away via ``apply_sparse``).
* ``apply_sparse`` — sort-based capacity dispatch (GShard/Switch-style token
  dropping): top-k FLOPs only, at the cost of gather/scatter + (under SPMD)
  dispatch collectives.  Used by the beyond-paper perf variant.

Expert parallelism: a layer may hold a share of the experts
(``MoEConfig.experts_held`` from ``first_expert``).  The router keeps all
``num_experts`` outputs and its top-k; the layer adds only the gated
outputs of those of a token's top-k that it holds — what one chip of an
expert-parallel deployment computes before the exchange.

Params:
    router: (D, E) f32
    experts: {w_gate/w_up: (E_held, D, Fe), w_down: (E_held, Fe, D)}
    shared:  {w_gate/w_up: (D, Sh*Fe), w_down: (Sh*Fe, D)}  (fused)
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.models.layers import activation, dense_init, linear

# sequence-chunk length for the dense dispatch path: bounds the live
# (B_local, chunk, E_local, Fe) intermediate to tens of MB per device.
_CHUNK = 256


def init(key, cfg: ModelConfig) -> dict:
    m = cfg.moe
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    E, D, Fe = m.held, cfg.d_model, m.d_expert

    def experts_init(k, d_in, d_out):
        keys = jax.random.split(k, E)
        return jax.vmap(lambda kk: dense_init(kk, d_in, d_out, dtype))(keys)

    p = {
        "router": dense_init(ks[0], D, m.num_experts, jnp.float32),  # f32
        "experts": {
            "w_gate": experts_init(ks[1], D, Fe),
            "w_up": experts_init(ks[2], D, Fe),
            "w_down": experts_init(ks[3], Fe, D),
        },
    }
    if m.num_shared_experts > 0:
        Fs = m.num_shared_experts * Fe
        p["shared"] = {
            "w_gate": dense_init(ks[4], D, Fs, dtype),
            "w_up": dense_init(ks[5], D, Fs, dtype),
            "w_down": dense_init(ks[6], Fs, D, dtype),
        }
    return p


def _route(p: dict, cfg: ModelConfig, h: jnp.ndarray):
    """Router over all ``num_experts``: softmax, top-k, the k gates
    renormalised.  Returns (combine (B,S,E_held) f32: each held expert's
    gate, zero where the token did not pick it; aux loss scalar; gates;
    idx (global expert ids))."""
    m = cfg.moe
    E, K = m.num_experts, m.top_k
    with jax.named_scope("moe_router"):
        logits = h.astype(jnp.float32) @ p["router"]  # (B,S,E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, K)  # (B,S,K)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # (B,S,K,E)
        combine = jnp.einsum("bske,bsk->bse", onehot, gate_vals)
        frac_tokens = jnp.mean(onehot.sum(axis=2), axis=(0, 1))  # (E,)
        frac_probs = jnp.mean(probs, axis=(0, 1))
        aux = E * jnp.sum(frac_tokens * frac_probs) * m.load_balance_coef
        if m.held != E:
            combine = combine[..., m.first_expert:m.first_expert + m.held]
    return combine, aux, gate_vals, gate_idx


def routed_rows(combine: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """(E_held,) int32: how many of the ``rows`` (a (B, S) bool mask) put
    each held expert among their top-k."""
    picked = (combine > 0) & rows[..., None]
    return picked.sum(axis=(0, 1), dtype=jnp.int32)


def _shared_out(p, cfg, h, *, lora=None, lora_mask=None, lora_scale=1.0):
    def _l(name):
        return None if lora is None else lora.get(name)

    sg = linear(h, p["shared"]["w_gate"], lora=_l("w_gate"),
                lora_mask=lora_mask, lora_scale=lora_scale)
    su = linear(h, p["shared"]["w_up"], lora=_l("w_up"),
                lora_mask=lora_mask, lora_scale=lora_scale)
    sy = activation(sg, cfg.act) * su
    return linear(sy, p["shared"]["w_down"], lora=_l("w_down"),
                  lora_mask=lora_mask, lora_scale=lora_scale)


def apply(
    p: dict,
    cfg: ModelConfig,
    h: jnp.ndarray,  # (B, S, D)
    *,
    lora: Optional[dict] = None,
    lora_mask: Optional[jnp.ndarray] = None,
    lora_scale: float = 1.0,
    count_rows: Optional[jnp.ndarray] = None,
):
    """Dense-dispatch MoE over the held experts.  Returns (out (B,S,D),
    aux_loss scalar), and ``routed_rows`` of the ``count_rows`` mask
    third where one is given."""
    B, S, D = h.shape
    combine, aux, _, _ = _route(p, cfg, h)
    counts = None if count_rows is None else routed_rows(combine, count_rows)
    hx = h
    chunk = min(_CHUNK, S)
    pad = (-S) % chunk
    if pad:
        hx = jnp.pad(hx, ((0, 0), (0, pad), (0, 0)))
        combine = jnp.pad(combine, ((0, 0), (0, pad), (0, 0)))
    nchunks = hx.shape[1] // chunk
    hx = jnp.moveaxis(hx.reshape(B, nchunks, chunk, D), 1, 0)
    cmb = jnp.moveaxis(
        combine.reshape(B, nchunks, chunk, -1), 1, 0
    )  # (n, B, chunk, E)

    ew = p["experts"]

    def one_chunk(args):
        hc, cc = args  # (B, chunk, D), (B, chunk, E)
        g = jnp.einsum("bsd,edf->bsef", hc, ew["w_gate"])
        u = jnp.einsum("bsd,edf->bsef", hc, ew["w_up"])
        y = activation(g, cfg.act) * u
        eo = jnp.einsum("bsef,efd->bsed", y, ew["w_down"])
        return jnp.einsum("bsed,bse->bsd", eo.astype(jnp.float32), cc)

    with jax.named_scope("moe_experts"):
        outs = jax.lax.map(one_chunk, (hx, cmb))  # (n, B, chunk, D) f32
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nchunks * chunk, D)[:, :S]
    out = out.astype(h.dtype)

    if "shared" in p:
        out = out + _shared_out(p, cfg, h, lora=lora, lora_mask=lora_mask,
                                lora_scale=lora_scale)
    if counts is not None:
        return out, aux, counts
    return out, aux


def apply_sparse(
    p: dict,
    cfg: ModelConfig,
    h: jnp.ndarray,
    *,
    capacity: Optional[int] = None,
    lora: Optional[dict] = None,
    lora_mask: Optional[jnp.ndarray] = None,
    lora_scale: float = 1.0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sort-based capacity dispatch: only top-k expert FLOPs per token.

    Tokens beyond an expert's capacity are dropped (their routed contribution
    is zero; the residual stream and shared experts still flow).
    """
    m = cfg.moe
    assert m.held == m.num_experts, \
        "capacity dispatch serves a layer that holds every expert"
    B, S, D = h.shape
    E, K = m.num_experts, m.top_k
    N = B * S
    NK = N * K
    cap = capacity or max(1, int(m.capacity_factor * NK / E))
    hf = h.reshape(N, D)

    combine, aux, gate_vals, gate_idx = _route(p, cfg, h)
    del combine
    flat_e = gate_idx.reshape(NK)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, jnp.arange(E))  # (E,)
    rank_sorted = jnp.arange(NK) - first[sorted_e]
    slot = jnp.zeros((NK,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))

    within = slot < cap
    s_idx = jnp.where(within, slot, cap)  # cap row = overflow bin
    tok = jnp.arange(NK) // K
    buf = jnp.zeros((E, cap + 1, D), h.dtype).at[flat_e, s_idx].set(hf[tok])
    xbuf = buf[:, :cap]  # (E, cap, D)

    ew = p["experts"]
    g = jnp.einsum("ecd,edf->ecf", xbuf, ew["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xbuf, ew["w_up"])
    y = activation(g, cfg.act) * u
    ybuf = jnp.einsum("ecf,efd->ecd", y, ew["w_down"])  # (E, cap, D)

    yk = ybuf[flat_e, jnp.minimum(s_idx, cap - 1)]  # (NK, D)
    w = gate_vals.reshape(NK) * within.astype(jnp.float32)
    out = jnp.einsum(
        "nkd,nk->nd",
        yk.reshape(N, K, D).astype(jnp.float32),
        w.reshape(N, K),
    ).reshape(B, S, D).astype(h.dtype)

    if "shared" in p:
        out = out + _shared_out(p, cfg, h, lora=lora, lora_mask=lora_mask,
                                lora_scale=lora_scale)
    return out, aux
