"""Pallas TPU paged flash-decode: one query token against a block-table KV
cache (the paged-KV companion of ``decode_attention.py``).

The cache is not a contiguous (B, Sk, KV, hd) array but a shared block
pool — ``k_pool``/``v_pool`` of shape (num_blocks, KV, block_size, hd),
head-major so each kv head's rows form one (block_size, hd) tile, plus a
per-sequence **block table** (B, nb) of physical block ids
(``serving/kv_pool.py``).  The table rides the grid as a *scalar-prefetch*
operand: Pallas reads it before the kernel body runs, so each grid step's
``BlockSpec`` index map can point the K/V/mask DMA at
``table[b, block_index]`` directly — key tiles are gathered from HBM by
the pipeline itself, and no dense per-sequence copy of the cache ever
materializes.  This is the serving hot path: the engine's decode step
attends straight out of the pool (``attention.decode_attention_step_paged``).

Ragged tails need no special casing: unallocated table entries hold the
pool's null block (id 0), whose validity mask is permanently all-False,
so a fully-masked tile contributes exact zeros to the online-softmax
recurrence (``m`` carries, ``corr = exp(0) = 1``).  The mask is per kv
head — eviction keeps different token positions per head — which the
dense Pallas decode kernel does not support; here the mask tile is
block-indexed like K/V, so per-head validity is free.

Sliding windows ride the same machinery: ``pos_pool`` tiles are
block-indexed exactly like the mask, and the query token's absolute
position (``new_pos``, per sequence) plus the window width are scalar-
prefetched next to the table, so the kernel applies
``new_pos - pos < window`` per key row with zero extra host logic — a
*traced* window (patterned local:global archs scan it through the layer
loop) takes this path too, it is just another prefetched scalar.

grid = (B, nb), key-block axis innermost with (m, l, acc) scratch carry
— the same flash-decode recurrence as ``decode_attention.py``, with the
key stream indirected through the table.  One grid step reads one whole
pool block (every kv head) and attends each kv head's GQA group of
``G = H // KV`` query rows against it, so K/V are read once per block,
not once per query head.

Oracle: ``ref.paged_decode_attention``.  jnp fallbacks in
``ops.paged_decode_attention`` (gather oracle at small depth, streaming
block scan beyond).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _step(j, nb, q_ref, k_ref, v_ref, oks, o_ref, mass_ref,
          m_scr, l_scr, acc_scr, *, scale, kv_heads):
    """Grid step ``j`` over one pool block.  ``oks(h)`` is the (1, bs)
    attendability of the block's rows for kv head ``h`` — the validity
    mask, optionally ANDed with the sliding-window predicate.

    Steps ``j < nb`` run the online-softmax recurrence; with ``mass_ref``
    the grid is twice as long and steps ``j >= nb`` revisit each block
    with the final (m, l) statistics to emit its normalized masses."""
    phase0 = j < nb

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    for h in range(kv_heads):
        ok = oks(h)  # (1, bs)
        q = q_ref[0, h].astype(jnp.float32)  # (G, hd)
        k = k_ref[0, h].astype(jnp.float32)  # (bs, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (G, bs)
        s = jnp.where(ok, s, NEG_INF)

        @pl.when(phase0)
        def _flash(h=h, s=s, ok=ok):
            v = v_ref[0, h].astype(jnp.float32)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            # the explicit where keeps fully-dead rows at l == 0 (m stays
            # NEG_INF, so exp(s - m) would be exp(0) = 1, not 0)
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + p.sum(axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new

        if mass_ref is not None:
            @pl.when(jnp.logical_not(phase0))
            def _masses(h=h, s=s, ok=ok):
                l = jnp.maximum(l_scr[h], 1e-30)
                mass_ref[0, 0, h] = jnp.where(ok, jnp.exp(s - m_scr[h]),
                                              0.0) / l

    @pl.when(j == nb - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _kernel(*refs, nb, scale, kv_heads, windowed, masses):
    n_pf = 3 if windowed else 1
    pf, refs = refs[:n_pf], refs[n_pf:]
    q_ref, k_ref, v_ref, mask_ref = refs[:4]
    refs = refs[4:]
    pos_ref = None
    if windowed:
        pos_ref, refs = refs[0], refs[1:]
    o_ref, refs = refs[0], refs[1:]
    mass_ref = None
    if masses:
        mass_ref, refs = refs[0], refs[1:]
    m_scr, l_scr, acc_scr = refs

    def oks(h):
        ok = mask_ref[0, h:h + 1, :]
        if windowed:
            npos_ref, win_ref = pf[1], pf[2]
            b = pl.program_id(0)
            ok = ok & ((npos_ref[b] - pos_ref[0, h:h + 1, :]) < win_ref[0])
        return ok

    _step(pl.program_id(1), nb, q_ref, k_ref, v_ref, oks, o_ref, mass_ref,
          m_scr, l_scr, acc_scr, scale=scale, kv_heads=kv_heads)


def _paged_call(q, k_pool, v_pool, mask_pool, table, *, pos_pool, new_pos,
                window, masses, interpret):
    B, H, hd = q.shape
    N, KV, bs, _ = k_pool.shape
    nb = table.shape[1]
    G = H // KV
    windowed = window is not None
    if windowed:
        assert pos_pool is not None and new_pos is not None, \
            "sliding-window masking needs pos_pool and new_pos"
    n_pf = 3 if windowed else 1  # table[, new_pos, window]
    steps = 2 * nb if masses else nb

    def ib(j):  # key-block index: a masses grid walks the blocks twice
        return jnp.where(j < nb, j, j - nb) if masses else j

    def blk(b, j, t, *_):  # pool block of this step, all kv heads
        return (t[b, ib(j)], 0, 0, 0)

    def vblk(b, j, t, *_):  # v is unread in phase 1: park on the null block
        return (jnp.where(j < nb, t[b, ib(j)], 0), 0, 0, 0)

    def meta(b, j, t, *_):
        return (t[b, ib(j)], 0, 0)

    in_specs = [
        pl.BlockSpec((1, KV, G, hd), lambda b, j, *_: (b, 0, 0, 0)),
        pl.BlockSpec((1, KV, bs, hd), blk),
        pl.BlockSpec((1, KV, bs, hd), vblk if masses else blk),
        pl.BlockSpec((1, KV, bs), meta),
    ]
    args = [q.reshape(B, KV, G, hd), k_pool, v_pool, mask_pool]
    if windowed:
        in_specs.append(pl.BlockSpec((1, KV, bs), meta))
        args.append(pos_pool)
    out_specs = [pl.BlockSpec((1, KV, G, hd), lambda b, j, *_: (b, 0, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype)]
    if masses:
        # phase-0 steps park on mass tile 0 — phase 1's first step
        # overwrites it before the pipeline's write-back moves on
        out_specs.append(pl.BlockSpec(
            (1, 1, KV, G, bs),
            lambda b, j, *_: (b, jnp.where(j < nb, 0, j - nb), 0, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, nb, KV, G, bs),
                                              jnp.float32))
    kernel = functools.partial(_kernel, nb=nb, scale=1.0 / (hd ** 0.5),
                               kv_heads=KV, windowed=windowed, masses=masses)
    prefetch = [table.astype(jnp.int32)]
    if windowed:
        prefetch += [new_pos.astype(jnp.int32),
                     jnp.asarray(window, jnp.int32).reshape(1)]
    res = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_pf,
            grid=(B, steps),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((KV, G, 1), jnp.float32),
                pltpu.VMEM((KV, G, 1), jnp.float32),
                pltpu.VMEM((KV, G, hd), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        interpret=interpret,
        name="paged_attention_masses" if masses else "paged_attention",
    )(*prefetch, *args)
    out = res[0].reshape(B, H, hd)
    if not masses:
        return out
    m = jnp.moveaxis(res[1], 1, 3)  # (B, KV, G, nb, bs)
    return out, m.reshape(B, H, nb * bs)


def paged_decode_attention_pallas(
    q: jnp.ndarray,  # (B, H, hd)
    k_pool: jnp.ndarray,  # (N, KV, block_size, hd) shared block pool
    v_pool: jnp.ndarray,
    mask_pool: jnp.ndarray,  # (N, KV, block_size) per-head slot validity
    table: jnp.ndarray,  # (B, nb) int32 physical block ids (0 = null)
    *,
    pos_pool: jnp.ndarray | None = None,  # (N, KV, block_size) int32
    new_pos: jnp.ndarray | None = None,  # (B,) query-token positions
    window=None,  # None | python int | traced int32 scalar
    interpret: bool = False,
) -> jnp.ndarray:
    """Flash decode over a paged cache.  Rows the caller considers dead
    (beyond the logical depth, or holding a stale previous owner's data)
    must be masked False in ``mask_pool`` — the mask is the single source
    of validity, exactly as in the dense cache layout.  With ``window``
    set, rows also need ``new_pos - pos < window`` to be attended
    (``pos_pool``/``new_pos`` become required); a sequence/head left with
    no attendable row returns exact zeros."""
    return _paged_call(q, k_pool, v_pool, mask_pool, table,
                       pos_pool=pos_pool, new_pos=new_pos, window=window,
                       masses=False, interpret=interpret)


# ---------------------------------------------------------------------------
# fused masses: decode attention + per-key softmax masses in one pass
# ---------------------------------------------------------------------------
#
# The decode-eviction scorer needs the probability mass the query token put
# on every cached row — the single-token analogue of the fused chunk-score
# kernel in ``chunk_attention.py``, and it reuses that kernel's two-phase
# trick: the grid's innermost axis runs 2*nb steps.  Phase 0 (j < nb) is the
# unmodified flash recurrence; once it ends, the scratch holds the *final*
# (m, l) statistics, so phase 1 (j >= nb) revisits each key tile, recomputes
# the scaled logits (cheap: one (G, hd) x (hd, block_size) matmul per kv
# head), and emits the normalized masses ``exp(s - m) / l`` per row.  V
# tiles park on the null block during phase 1 (they are not read).  The
# attention output is *bitwise* the plain kernel's: phase 0 is the same
# instruction sequence.


def paged_decode_masses_pallas(
    q: jnp.ndarray,  # (B, H, hd)
    k_pool: jnp.ndarray,  # (N, KV, block_size, hd)
    v_pool: jnp.ndarray,
    mask_pool: jnp.ndarray,  # (N, KV, block_size)
    table: jnp.ndarray,  # (B, nb) int32
    *,
    pos_pool: jnp.ndarray | None = None,
    new_pos: jnp.ndarray | None = None,
    window=None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Paged flash decode that also returns the query's normalized softmax
    masses over every table row: (out (B, H, hd), masses (B, H, nb*bs) f32).
    ``out`` is bitwise ``paged_decode_attention_pallas``; masked rows carry
    exact-zero mass.  Oracle: ``ref.paged_decode_masses``."""
    return _paged_call(q, k_pool, v_pool, mask_pool, table,
                       pos_pool=pos_pool, new_pos=new_pos, window=window,
                       masses=True, interpret=interpret)
