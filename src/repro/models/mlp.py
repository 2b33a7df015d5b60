"""Gated MLP (SwiGLU / GeGLU) block with lookahead-LoRA hooks.

Params: {"w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.common.sharding import column_split
from repro.models.layers import activation, dense_init, linear


def init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    f = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, cfg.d_model, f, dtype),
        "w_up": dense_init(k2, cfg.d_model, f, dtype),
        "w_down": dense_init(k3, f, cfg.d_model, dtype),
    }


def apply(
    p: dict,
    cfg: ModelConfig,
    h: jnp.ndarray,
    *,
    lora: Optional[dict] = None,
    lora_mask: Optional[jnp.ndarray] = None,
    lora_scale: float = 1.0,
) -> jnp.ndarray:
    def _l(name):
        return None if lora is None else lora.get(name)

    g = linear(h, p["w_gate"], lora=_l("w_gate"), lora_mask=lora_mask,
               lora_scale=lora_scale)
    u = linear(h, p["w_up"], lora=_l("w_up"), lora_mask=lora_mask,
               lora_scale=lora_scale)
    y = activation(g, cfg.act) * u
    return linear(y, p["w_down"], lora=_l("w_down"), lora_mask=lora_mask,
                  lora_scale=lora_scale)


def apply_sharded(
    p: dict,
    cfg: ModelConfig,
    h: jnp.ndarray,
    smesh,
    *,
    lora: Optional[dict] = None,
    lora_mask: Optional[jnp.ndarray] = None,
    lora_scale: float = 1.0,
) -> jnp.ndarray:
    """``apply`` under manual tensor parallelism, bit-exact vs ``apply``.

    Column-parallel gate/up (full d_model contraction per local d_ff
    column), elementwise gating on the local columns, then the activation
    is all-gathered *inside* shard_map so w_down — column-parallel on its
    *output* dim — contracts the full d_ff in single-device order.  No
    psum ever touches a reduction, which is what GSPMD cannot promise:
    its dot realization is shape-dependent and may re-associate the bf16
    sums.  LoRA deltas ride along (A replicated → full contraction, B
    column-sliced like its base weight).  Falls back to a replicated
    (redundant but exact) evaluation when the mesh doesn't divide d_ff
    or d_model.
    """
    if smesh is None:
        return apply(p, cfg, h, lora=lora, lora_mask=lora_mask,
                     lora_scale=lora_scale)
    from repro.models import attention as attn_mod

    msize = int(smesh.shape["model"])
    if p["w_gate"].shape[1] % msize or p["w_down"].shape[1] % msize:
        return attn_mod.replicated_apply(
            lambda hh, pp, lo, lm: apply(pp, cfg, hh, lora=lo, lora_mask=lm,
                                         lora_scale=lora_scale),
            smesh, h, p, lora, lora_mask)
    from jax.sharding import PartitionSpec as P

    bspec = attn_mod._batch_spec(smesh, h.shape[0])
    names = ("w_gate", "w_up", "w_down")
    have_lora = lora is not None and lora_mask is not None
    lsub = {n: lora[n] for n in names
            if have_lora and lora.get(n) is not None}

    def local(hh, pp, *rest):
        lo = rest[0] if have_lora else {}
        lm = rest[1] if have_lora else None

        def _l(name):
            return lo.get(name)

        g = linear(hh, pp["w_gate"], lora=_l("w_gate"), lora_mask=lm,
                   lora_scale=lora_scale)
        u = linear(hh, pp["w_up"], lora=_l("w_up"), lora_mask=lm,
                   lora_scale=lora_scale)
        y = activation(g, cfg.act) * u
        yf = jax.lax.all_gather(y, "model", axis=2, tiled=True)
        return linear(yf, pp["w_down"], lora=_l("w_down"), lora_mask=lm,
                      lora_scale=lora_scale)

    arrs = [h, {n: p[n] for n in names}]
    specs = [P(bspec, None, None), {n: column_split() for n in names}]
    if have_lora:
        arrs += [lsub, lora_mask]
        specs += [{n: {"a": P(None, None), "b": column_split()}
                   for n in lsub},
                  P(bspec, None, None)]
    return attn_mod._shard_map(local, smesh, tuple(specs),
                               P(bspec, None, "model"))(*arrs)
