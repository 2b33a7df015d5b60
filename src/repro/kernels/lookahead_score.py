"""Pallas TPU kernel for LookaheadKV importance scores — the paper's hot spot.

Computes, per (batch, q-head), the mean over observation rows of the softmax
probability mass each prompt key receives:

    scores[b, h, j] = 1/n_obs · Σ_i  softmax_row_i(q_obs · Kᵀ / √d)[j]

TPU adaptation (DESIGN.md §3): the observation block (n_obs ≤ 128 rows of
hd ≤ 256) stays resident in VMEM; keys stream HBM→VMEM in (block_k, hd)
tiles.  Per-key normalized mass needs the *final* row normalizers, so the
grid runs the key axis twice (phase trick): phase 0 accumulates the online
(m, l) statistics into scratch, phase 1 re-streams each key tile and emits
``exp(s − m)/l`` column means directly — the (n_obs × Sk) score matrix never
hits HBM, and output traffic is Sk floats per head instead of n_obs·Sk.

grid = (B, H, 2·nk); phase = ik // nk.  Operands are head-major — q_obs
(B, H, n_obs, hd), keys (B, KV, Sk, hd) — so every tile's minor dimensions
are (rows, hd); the wrapper transposes and pads the key axis to
lane-aligned tiles.

This is the one masked streaming scoring primitive every observation-style
policy rides (chunked *and* monolithic prefill):

* ``q_offset`` is a *scalar-prefetched* (traced) observation-row base
  position, so one compiled program serves the deferred observation-window
  scoring of the snapkv family at any (traced) prompt length;
* ``window`` applies the sliding-window visibility of local layers;
* ``row_valid`` zeroes invalid observation rows (bucket padding) — they
  contribute exact zeros to the mean, whose denominator stays ``n_obs``
  (callers wanting a sum over valid rows rescale by ``n_obs``).

Oracle: ``ref.lookahead_score``.  jnp fallback: ``ops._chunked_lookahead_score``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import head_major, key_tiling

NEG_INF = -1e30


def _kernel(offs_ref, q_ref, k_ref, mask_ref, rv_ref, o_ref, m_scr, l_scr, *,
            n_obs, block_k, nk, scale, window):
    j = pl.program_id(2)
    ik = jnp.where(j < nk, j, j - nk)
    phase1 = j >= nk
    q0 = offs_ref[0]  # absolute position of obs row 0 (traced)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (n_obs, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (block_k, hd)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (n_obs, block_k)

    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (n_obs, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (n_obs, block_k), 1)
    ok = k_pos <= q_pos  # causal among obs rows; prompt keys all visible
    if window is not None:
        ok &= (q_pos - k_pos) < window
    ok &= mask_ref[0]  # (1, block_k) key validity (padding / evicted)
    s = jnp.where(ok, s, NEG_INF)

    @pl.when(jnp.logical_not(phase1))
    def _pass1():
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_scr[...] = (l_scr[...] * jnp.exp(m_prev - m_new)
                      + p.sum(axis=-1, keepdims=True))
        m_scr[...] = m_new

    @pl.when(phase1)
    def _pass2():
        l = jnp.maximum(l_scr[...], 1e-30)
        p = jnp.where(ok, jnp.exp(s - m_scr[...]), 0.0) / l
        p = p * rv_ref[0]  # (n_obs, 1) real-row weights
        o_ref[0, 0] = (p.sum(axis=0, keepdims=True) / n_obs).astype(
            o_ref.dtype)


def lookahead_score_pallas(
    q_obs: jnp.ndarray,  # (B, n_obs, H, hd)
    k: jnp.ndarray,  # (B, Sk, KV, hd) — prompt keys then obs keys
    n_prompt: int,
    *,
    kv_mask: jnp.ndarray | None = None,  # (B, n_prompt)
    window: int | None = None,
    q_offset=None,  # scalar int32 (may be traced); default n_prompt
    row_valid: jnp.ndarray | None = None,  # (B, n_obs) real-row mask
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    B, n_obs, H, hd = q_obs.shape
    Sk, KV = k.shape[1], k.shape[2]
    group = H // KV
    scale = 1.0 / (hd ** 0.5)
    if window == 0:
        window = None

    block_k, Skp = key_tiling(Sk, block_k)
    full_mask = jnp.ones((B, Sk), bool)
    if kv_mask is not None:
        full_mask = full_mask.at[:, :n_prompt].set(kv_mask)
    full_mask = jnp.pad(full_mask, ((0, 0), (0, Skp - Sk)))[:, None, :]
    if row_valid is None:
        row_valid = jnp.ones((B, n_obs), bool)
    rv = row_valid.astype(jnp.float32)[..., None]  # (B, n_obs, 1)
    nk = Skp // block_k
    offs = jnp.reshape(
        jnp.asarray(n_prompt if q_offset is None else q_offset, jnp.int32),
        (1,))

    kernel = functools.partial(
        _kernel, n_obs=n_obs, block_k=block_k, nk=nk, scale=scale,
        window=window,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, 2 * nk),
        in_specs=[
            pl.BlockSpec((1, 1, n_obs, hd), lambda b, h, j, offs: (b, h, 0, 0)),
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda b, h, j, offs, g=group, nk=nk: (
                    b, h // g, jnp.where(j < nk, j, j - nk), 0
                ),
            ),
            pl.BlockSpec(
                (1, 1, block_k),
                lambda b, h, j, offs, nk=nk: (
                    b, 0, jnp.where(j < nk, j, j - nk)),
            ),
            pl.BlockSpec((1, n_obs, 1), lambda b, h, j, offs: (b, 0, 0)),
        ],
        # phase-0 iterations park on block 0 (never written by the kernel in
        # that phase; phase 1's first iteration overwrites it before any
        # write-back escapes), phase-1 iterations emit block ik.
        out_specs=pl.BlockSpec(
            (1, 1, 1, block_k),
            lambda b, h, j, offs, nk=nk: (b, h, 0,
                                          jnp.where(j < nk, 0, j - nk)),
        ),
        scratch_shapes=[
            pltpu.VMEM((n_obs, 1), jnp.float32),
            pltpu.VMEM((n_obs, 1), jnp.float32),
        ],
    )
    scores = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, Skp), jnp.float32),
        interpret=interpret,
        name="lookahead_score",
    )(offs, head_major(q_obs), head_major(k, Skp), full_mask, rv)
    return scores[:, :, 0, :n_prompt]
