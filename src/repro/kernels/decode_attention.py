"""Pallas TPU flash-decode: one query token against a long (possibly evicted)
KV cache.

Decode is memory-bound: the roofline term is cache bytes / HBM bandwidth, so
the kernel's job is to stream K/V tiles exactly once at full bandwidth while
the (G × block_k) score tile lives in registers/VMEM.  grid = (B, KV, nk),
key axis innermost with (m, l, acc) scratch carry — the flash-attention
recurrence specialized to one query row per head, each kv head's GQA
group of ``G = H // KV`` query rows attending one (block_k, hd) key tile.
The wrapper hands the kernel head-major K/V (B, KV, Sk, hd), keys padded
to lane-aligned tiles.

Oracle: ``ref.decode_attention``.  jnp fallback in ``ops.decode_attention``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import head_major, key_tiling

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr, *,
            nk, scale):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (block_k, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    ok = mask_ref[0]  # (1, block_k)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (G, block_k)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jnp.ndarray,  # (B, H, hd)
    k: jnp.ndarray,  # (B, Sk, KV, hd)
    v: jnp.ndarray,
    *,
    kv_mask: jnp.ndarray | None = None,  # (B, Sk)
    block_k: int = 1024,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    block_k, Skp = key_tiling(Sk, block_k)
    mask = jnp.ones((B, Sk), bool) if kv_mask is None else kv_mask
    mask = jnp.pad(mask, ((0, 0), (0, Skp - Sk)))[:, None, :]
    nk = Skp // block_k
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_kernel, nk=nk, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, ik: (b, 0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention",
    )(q.reshape(B, KV, G, hd), head_major(k, Skp), head_major(v, Skp), mask)
    return out.reshape(B, H, hd)
