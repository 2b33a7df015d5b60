"""Pallas TPU cross-chunk flash attention (streaming / chunked prefill).

One query chunk of ``C`` rows starting at absolute position ``q_offset``
attends over the materialized key/value buffer: prior-chunk keys are fully
visible, the chunk attends itself causally, and buffer columns at or past
the chunk end are causally invisible (buffer column ``j`` holds the token
at absolute position ``j``).  ``q_offset`` is a *traced* scalar — one
compiled program serves every chunk index of every prompt length, which is
what lets the serving compile cache drop the prompt-length bucket ladder.

Tiling: grid = (B, H, nk) with the key axis innermost (sequential).  The
whole chunk rides in VMEM as a single (C, hd) query tile; key blocks whose
first column lies beyond the chunk's last visible position are skipped
(the usual causal block pruning — for an early chunk of a long buffer
almost every key block short-circuits).  The kernels take head-major
operands — q (B, H, C, hd), k/v (B, KV, K, hd) — so every tile's two
minor dimensions are (rows, hd), the layout Mosaic tiles (8·k, 128·k);
``ops.chunk_attention`` transposes the sequence-major model buffers and
pads the key axis to lane-aligned tiles.

GQA is handled in the index map (query head ``h`` reads kv head
``h // group``).  Oracle: ``ref.attention`` with explicit ``q_pos``.
jnp fallback with identical math: ``ops.chunk_attention``'s direct path.

Fused score accumulation
------------------------
``chunk_attention_masses_pallas`` additionally emits the chunk's summed
softmax *column masses* per key — the streaming eviction-score partial the
cumulative (h2o) policy accumulates across chunks — without ever
materializing the (C, K) probability block.  Per-key normalized mass needs
the *final* per-row softmax statistics, so the fused kernel runs the key
axis twice (the same phase trick as ``lookahead_score``): phase 0 is the
unmodified online-softmax attention pass (the attention output is
bit-identical to the unfused kernel); phase 1 re-streams each key tile and
emits ``Σ_rows exp(s − m)/l`` column sums, zeroing rows at or past the true
prompt length (``n_total``) so padded chunk rows contribute nothing.
Output traffic for the scores is K floats per (batch, head) instead of
C·K.  Oracle: ``ref.chunk_column_masses``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import head_major, key_tiling

NEG_INF = -1e30


def _tile(q_ref, k_ref, s0, ik, *, window, block_k, C, scale):
    """(s, ok): scaled logits of the chunk against key tile ``ik`` and
    their causal / windowed visibility."""
    q = q_ref[0, 0].astype(jnp.float32)  # (C, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (C, bk)
    q_pos = s0 + jax.lax.broadcasted_iota(jnp.int32, (C, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (C, block_k), 1)
    ok = k_pos <= q_pos
    if window is not None:
        ok &= (q_pos - k_pos) < window
    return jnp.where(ok, s, NEG_INF), ok


def _online_update(s, ok, v_ref, m_scr, l_scr, acc_scr):
    """One key tile of the online-softmax recurrence; (C, 1) row stats."""
    v = v_ref[0, 0].astype(jnp.float32)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new


def _kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            window, block_k, nk, C, scale):
    ik = pl.program_id(2)
    s0 = offs_ref[0]  # absolute position of q row 0

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal block pruning: the chunk's last row sits at s0 + C - 1; key
    # blocks starting past it contain no visible column for any row.
    @pl.when(ik * block_k <= s0 + C - 1)
    def _compute():
        s, ok = _tile(q_ref, k_ref, s0, ik, window=window, block_k=block_k,
                      C=C, scale=scale)
        _online_update(s, ok, v_ref, m_scr, l_scr, acc_scr)

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _scratch(C: int, hd: int) -> list:
    return [pltpu.VMEM((C, 1), jnp.float32), pltpu.VMEM((C, 1), jnp.float32),
            pltpu.VMEM((C, hd), jnp.float32)]


def chunk_attention_pallas(
    q: jnp.ndarray,  # (B, C, H, hd) rotary-encoded chunk queries
    k: jnp.ndarray,  # (B, K, KV, hd) key buffer (col j = position j)
    v: jnp.ndarray,
    q_offset,  # scalar int32 (may be traced) — position of q row 0
    *,
    window: int | None = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    B, C, H, hd = q.shape
    K, KV = k.shape[1], k.shape[2]
    group = H // KV
    block_k, Kp = key_tiling(K, block_k)
    nk = Kp // block_k
    scale = 1.0 / (hd ** 0.5)
    if window == 0:
        window = None
    offs = jnp.reshape(jnp.asarray(q_offset, jnp.int32), (1,))

    kernel = functools.partial(
        _kernel, window=window, block_k=block_k, nk=nk, C=C, scale=scale,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nk),
        in_specs=[
            pl.BlockSpec((1, 1, C, hd), lambda b, h, ik, offs: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, ik, offs, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, ik, offs, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C, hd),
                               lambda b, h, ik, offs: (b, h, 0, 0)),
        scratch_shapes=_scratch(C, hd),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, C, hd), q.dtype),
        interpret=interpret,
        # a windowed layer's calls carry a name of their own, so the trace
        # tells the two kinds of layer apart
        name="chunk_attention" if window is None else "chunk_attention_window",
    )(offs, head_major(q), head_major(k, Kp), head_major(v, Kp))
    return jnp.swapaxes(out, 1, 2)


def _fused_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, mass_ref,
                  m_scr, l_scr, acc_scr, *, window, block_k, nk, C, scale):
    j = pl.program_id(2)
    ik = jnp.where(j < nk, j, j - nk)
    phase1 = j >= nk
    s0 = offs_ref[0]  # absolute position of q row 0
    n_total = offs_ref[1]  # true prompt length (rows >= it score zero)
    # causal block pruning (see the single-pass kernel): key blocks starting
    # past the chunk's last row are invisible to every query row
    live = ik * block_k <= s0 + C - 1

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile():
        return _tile(q_ref, k_ref, s0, ik, window=window, block_k=block_k,
                     C=C, scale=scale)

    # phase 0: the unmodified online-softmax attention recurrence — the
    # attention output is bit-identical to the single-pass kernel's.
    @pl.when(jnp.logical_not(phase1) & live)
    def _pass1():
        s, ok = tile()
        _online_update(s, ok, v_ref, m_scr, l_scr, acc_scr)

    @pl.when(j == nk - 1)
    def _finish_o():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)

    # phase 1: the (m, l) statistics are final — re-stream the key tile and
    # emit its normalized column masses, zeroing invalid (padded) rows.
    @pl.when(phase1 & live)
    def _pass2():
        s, ok = tile()
        l = jnp.maximum(l_scr[...], 1e-30)
        p = jnp.where(ok, jnp.exp(s - m_scr[...]), 0.0) / l
        row = s0 + jax.lax.broadcasted_iota(jnp.int32, (C, block_k), 0)
        p = jnp.where(row < n_total, p, 0.0)
        mass_ref[0, 0] = p.sum(axis=0, keepdims=True)

    @pl.when(phase1 & jnp.logical_not(live))
    def _pass2_pruned():  # causally invisible tile: exact zero mass
        mass_ref[0, 0] = jnp.zeros((1, block_k), jnp.float32)


def chunk_attention_masses_pallas(
    q: jnp.ndarray,  # (B, C, H, hd) rotary-encoded chunk queries
    k: jnp.ndarray,  # (B, K, KV, hd) key buffer (col j = position j)
    v: jnp.ndarray,
    q_offset,  # scalar int32 (may be traced) — position of q row 0
    n_total,  # scalar int32 (may be traced) — true prompt length
    *,
    window: int | None = None,
    block_k: int = 512,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused attention + streaming score partials.

    Returns (out (B, C, H, hd), masses (B, H, K) f32) where
    ``masses[b, h, j] = Σ_{i: q_offset+i < n_total} softmax_row_i[j]`` —
    the h2o column-mass contribution of this chunk, computed tile-by-tile
    without materializing the probability block.  ``out`` is bit-identical
    to ``chunk_attention_pallas``.
    """
    B, C, H, hd = q.shape
    K, KV = k.shape[1], k.shape[2]
    group = H // KV
    block_k, Kp = key_tiling(K, block_k)
    nk = Kp // block_k
    scale = 1.0 / (hd ** 0.5)
    if window == 0:
        window = None
    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32).reshape(()),
                      jnp.asarray(n_total, jnp.int32).reshape(())])

    kernel = functools.partial(
        _fused_kernel, window=window, block_k=block_k, nk=nk, C=C,
        scale=scale,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, 2 * nk),
        in_specs=[
            pl.BlockSpec((1, 1, C, hd), lambda b, h, j, offs: (b, h, 0, 0)),
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda b, h, j, offs, g=group, nk=nk: (
                    b, h // g, jnp.where(j < nk, j, j - nk), 0
                ),
            ),
            # v is only read in phase 0; phase-1 iterations park on block 0
            # so the mass sweep doesn't re-stream the whole v buffer
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda b, h, j, offs, g=group, nk=nk: (
                    b, h // g, jnp.where(j < nk, j, 0), 0
                ),
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, C, hd), lambda b, h, j, offs: (b, h, 0, 0)),
            # phase-0 iterations park on mass block 0 (key block 0 is never
            # causally pruned, so phase 1's first iteration overwrites it
            # before any write-back escapes); phase 1 emits block ik.  The
            # unit axis keeps the block's minor dims (1, block_k) tileable.
            pl.BlockSpec(
                (1, 1, 1, block_k),
                lambda b, h, j, offs, nk=nk: (b, h, 0, jnp.where(j < nk, 0,
                                                                 j - nk)),
            ),
        ],
        scratch_shapes=_scratch(C, hd),
    )
    out, masses = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, C, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, Kp), jnp.float32),
        ],
        interpret=interpret,
        name="chunk_attention_masses",
    )(offs, head_major(q), head_major(k, Kp), head_major(v, Kp))
    return jnp.swapaxes(out, 1, 2), masses[:, :, 0, :K]
