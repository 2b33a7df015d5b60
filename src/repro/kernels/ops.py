"""Public, jit-friendly wrappers for every kernel.

Dispatch policy
---------------
* On TPU (``jax.default_backend() == "tpu"``) or when ``REPRO_FORCE_PALLAS=1``
  (used by the interpret-mode kernel tests), the Pallas kernels in this
  package are used.
* Otherwise a memory-bounded, pure-jnp *chunked* implementation runs.  These
  fallbacks implement the same streaming algorithms as the kernels (online
  softmax, chunked SSD) so the CPU dry-run lowers with bounded temporaries —
  which is what the roofline reads.
* ``reference_mode()`` overrides both: the Pallas kernels are forward-only
  (no custom VJP), so any code that must trace under ``jax.grad`` — the
  distillation objective — wraps its forward pass in this context and gets
  the differentiable jnp path regardless of backend or env.

Every wrapper has a matching naive oracle in ``ref.py``.
"""

from __future__ import annotations

import contextlib
import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels.ref import NEG_INF, _expand_gqa

# Sequence lengths at or below this threshold just call the naive path: the
# full score block is small enough that chunking only adds overhead.
_DIRECT_SEQ = 2048

# Trace-time override: when truthy, use_pallas() is False no matter what.
# Only mutated by reference_mode(); read at trace time, so a jitted function
# traced inside the context bakes in the jnp path.
_REFERENCE_ONLY = False


@contextlib.contextmanager
def reference_mode():
    """Force the differentiable jnp dispatch path while tracing.

    The Pallas kernels have no custom VJP — differentiating through
    ``pallas_call`` raises.  Training code (``core/objective``) traces its
    forward pass inside this context so gradients flow through the jnp
    implementations on every backend, including TPU and REPRO_FORCE_PALLAS=1.
    """
    global _REFERENCE_ONLY
    prev = _REFERENCE_ONLY
    _REFERENCE_ONLY = True
    try:
        yield
    finally:
        _REFERENCE_ONLY = prev


def use_pallas() -> bool:
    if _REFERENCE_ONLY:
        return False
    if os.environ.get("REPRO_FORCE_PALLAS") == "1":
        return True
    return jax.default_backend() == "tpu"


def _pallas_interpret() -> bool:
    """interpret=True whenever we are not actually on a TPU."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# flash attention (prefill)
# ---------------------------------------------------------------------------


def flash_attention(
    q: jnp.ndarray,  # (B, Sq, H, hd)
    k: jnp.ndarray,  # (B, Sk, KV, hd)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window=None,  # None = unbounded; python int (static) or traced int32 scalar
    q_offset: int = 0,
    kv_mask: jnp.ndarray | None = None,
    block_q: int = 512,
    block_k: int = 1024,
) -> jnp.ndarray:
    """Masked (GQA) attention with bounded temporaries.

    ``q_offset`` is the absolute position of q row 0 relative to k row 0
    (prefill: Sk - Sq when queries are the tail of the key sequence).
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    static_window = window is None or isinstance(window, int)
    if (use_pallas() and kv_mask is None and Sq == Sk and q_offset == 0
            and static_window):
        from repro.kernels import flash_attention as fk

        return fk.flash_attention_pallas(
            q, k, v, causal=causal, window=window,
            block_q=min(block_q, Sq), block_k=min(block_k, Sk),
            interpret=_pallas_interpret(),
        )
    if Sk <= _DIRECT_SEQ:
        from repro.kernels import ref

        q_pos = jnp.broadcast_to(q_offset + jnp.arange(Sq), (B, Sq))
        return ref.attention(
            q, k, v, causal=causal, window=window,
            q_pos=q_pos, kv_mask=kv_mask,
        )
    return _chunked_attention(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        kv_mask=kv_mask, block_q=block_q, block_k=block_k,
    )


def _chunked_attention(q, k, v, *, causal, window, q_offset, kv_mask,
                       block_q, block_k):
    """Online-softmax attention: scan over q blocks × k blocks (jnp flash)."""
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    group = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    qf = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kf = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vf = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    kvm = jnp.ones((B, Sk), bool) if kv_mask is None else kv_mask
    kvm = jnp.pad(kvm, ((0, 0), (0, pad_k)))
    nq, nk = qf.shape[1] // block_q, kf.shape[1] // block_k
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    kf = kf.reshape(B, nk, block_k, KV, hd)
    vf = vf.reshape(B, nk, block_k, KV, hd)
    kvm = kvm.reshape(B, nk, block_k)
    qf = qf.reshape(B, nq, block_q, H, hd)

    def q_block(iq, qb):
        # qb: (B, block_q, H, hd)
        q_pos = q_offset + iq * block_q + jnp.arange(block_q)

        def k_block(carry, inputs):
            m, l, acc = carry  # (B,H,bq), (B,H,bq), (B,H,bq,hd)
            ik, kb, vb, mb = inputs
            k_pos = ik * block_k + jnp.arange(block_k)
            kbf = _expand_gqa(kb, group)
            vbf = _expand_gqa(vb, group)
            s = jnp.einsum(
                "bqhd,bkhd->bhqk", qb.astype(jnp.float32),
                kbf.astype(jnp.float32),
            ) * scale
            ok = jnp.ones((block_q, block_k), bool)
            if causal:
                ok &= k_pos[None, :] <= q_pos[:, None]
            if window is not None:
                ok &= (q_pos[:, None] - k_pos[None, :]) < window
            ok = ok[None, :, :] & mb[:, None, :]
            s = jnp.where(ok[:, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vbf.astype(jnp.float32))
            return (m_new, l, acc), None

        init = (
            jnp.full((B, H, block_q), NEG_INF, jnp.float32),
            jnp.zeros((B, H, block_q), jnp.float32),
            jnp.zeros((B, H, block_q, hd), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(
            k_block, init,
            (jnp.arange(nk), jnp.moveaxis(kf, 1, 0), jnp.moveaxis(vf, 1, 0),
             jnp.moveaxis(kvm, 1, 0)),
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 1, 2)  # (B, block_q, H, hd)

    outs = jax.lax.map(lambda args: q_block(*args),
                       (jnp.arange(nq), jnp.moveaxis(qf, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * block_q, H, hd)
    return out[:, :Sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# cross-chunk attention (streaming / chunked prefill)
# ---------------------------------------------------------------------------


def chunk_attention(
    q: jnp.ndarray,  # (B, C, H, hd) rotary-encoded chunk queries
    k: jnp.ndarray,  # (B, K, KV, hd) materialized key buffer (col j = pos j)
    v: jnp.ndarray,
    *,
    q_offset,  # scalar int32 (usually traced) — position of q row 0
    window=None,  # None | python int | traced int32 scalar
    score_masses: bool = False,  # also emit summed softmax column masses
    n_total=None,  # scalar int32 — rows at/past it contribute zero mass
    block_q: int = 256,
    block_k: int = 1024,
):
    """Attention of one prefill chunk over the prompt-so-far buffer.

    Prior keys (columns < ``q_offset``) are fully visible, the chunk is
    causal within itself, and columns at or beyond the chunk end are
    causally invisible — so the buffer may be deeper than the tokens
    streamed so far without any explicit validity mask.  ``q_offset`` is
    traced: one compiled program serves every chunk position.

    With ``score_masses=True`` the return value is ``(out, masses)`` where
    ``masses[b, h, j] = Σ_i softmax_row_i[j]`` over the chunk's *valid*
    rows (``q_offset + i < n_total``; all rows when ``n_total`` is None) —
    the cumulative (h2o) eviction-score partial, fused into the streaming
    pass so the (C, K) probability block never materializes on the Pallas
    or large-buffer paths.  The small-buffer jnp path scores through the
    dense ``ref.chunk_column_masses`` oracle (chunking only adds overhead
    there, and the dense sum preserves bit-exact chunked-vs-monolithic
    eviction parity on CPU).
    """
    B, C, H, hd = q.shape
    K = k.shape[1]
    static_window = window is None or isinstance(window, int)
    if use_pallas() and static_window:
        from repro.kernels import chunk_attention as ck

        if score_masses:
            nt = q_offset + C if n_total is None else n_total
            return ck.chunk_attention_masses_pallas(
                q, k, v, q_offset, nt, window=window,
                block_k=min(block_k, K), interpret=_pallas_interpret(),
            )
        return ck.chunk_attention_pallas(
            q, k, v, q_offset, window=window, block_k=min(block_k, K),
            interpret=_pallas_interpret(),
        )
    row_valid = None
    if score_masses and n_total is not None:
        row_valid = jnp.broadcast_to(
            (jnp.asarray(q_offset, jnp.int32) + jnp.arange(C))[None]
            < n_total, (B, C))
    if K <= _DIRECT_SEQ:
        from repro.kernels import ref

        q_pos = jnp.broadcast_to(
            jnp.asarray(q_offset, jnp.int32) + jnp.arange(C), (B, C))
        out = ref.attention(q, k, v, causal=True, window=window, q_pos=q_pos)
        if score_masses:
            masses = ref.chunk_column_masses(
                q, k, q_offset=q_offset, window=window, row_valid=row_valid)
            return out, masses
        return out
    out = _chunked_attention(
        q, k, v, causal=True, window=window, q_offset=q_offset,
        kv_mask=None, block_q=block_q, block_k=block_k,
    )
    if score_masses:
        masses = _chunked_lookahead_score(
            q, k, K, kv_mask=None, window=window, q_offset=q_offset,
            row_valid=row_valid, reduce="sum", block_k=block_k,
        )
        return out, masses
    return out


# ---------------------------------------------------------------------------
# decode attention (single new token vs long cache)
# ---------------------------------------------------------------------------


def decode_attention(
    q: jnp.ndarray,  # (B, H, hd)
    k: jnp.ndarray,  # (B, Sk, KV, hd)
    v: jnp.ndarray,
    *,
    kv_mask: jnp.ndarray | None = None,  # (B, Sk) or (B, Sk, KV) per-head
    block_k: int = 2048,
) -> jnp.ndarray:
    B, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    if use_pallas() and (kv_mask is None or kv_mask.ndim == 2):
        from repro.kernels import decode_attention as dk

        return dk.decode_attention_pallas(
            q, k, v, kv_mask=kv_mask, block_k=min(block_k, Sk),
            interpret=_pallas_interpret(),
        )
    # Single-query decode: always take the direct einsum on the jnp path.
    # The (B, H, Sk) logits are small (one row per sequence), and — crucially
    # for SPMD — the direct form lets XLA keep a sequence-sharded cache
    # sharded (partial softmax + tiny all-reduces).  The chunked fallback
    # below scans over key blocks, which *gathers* a seq-sharded cache every
    # block (§Perf decode iteration 1, refuted-then-fixed hypothesis).
    if kv_mask is None or kv_mask.ndim in (2, 3):
        from repro.kernels import ref

        return ref.decode_attention(q, k, v, kv_mask=kv_mask)
    group = H // KV
    pad = (-Sk) % block_k
    kf = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vf = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if kv_mask is None:
        kvm = jnp.ones((B, Sk, KV), bool)
    elif kv_mask.ndim == 2:
        kvm = jnp.broadcast_to(kv_mask[..., None], (B, Sk, KV))
    else:
        kvm = kv_mask
    kvm = jnp.pad(kvm, ((0, 0), (0, pad), (0, 0)))
    nk = kf.shape[1] // block_k
    kf = jnp.moveaxis(kf.reshape(B, nk, block_k, KV, hd), 1, 0)
    vf = jnp.moveaxis(vf.reshape(B, nk, block_k, KV, hd), 1, 0)
    kvm = jnp.moveaxis(kvm.reshape(B, nk, block_k, KV), 1, 0)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    def body(carry, inputs):
        m, l, acc = carry
        kb, vb, mb = inputs
        kbf = _expand_gqa(kb, group).astype(jnp.float32)
        vbf = _expand_gqa(vb, group).astype(jnp.float32)
        s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), kbf) * scale
        # mb: (B, block_k, KV) -> (B, H, block_k)
        mh = jnp.repeat(jnp.moveaxis(mb, 2, 1), group, axis=1)
        s = jnp.where(mh, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bhk,bkhd->bhd", p, vbf)
        return (m_new, l, acc), None

    init = (
        jnp.full((B, H), NEG_INF, jnp.float32),
        jnp.zeros((B, H), jnp.float32),
        jnp.zeros((B, H, hd), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, (kf, vf, kvm))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


# ---------------------------------------------------------------------------
# paged decode attention (block-table KV pool)
# ---------------------------------------------------------------------------


def paged_decode_attention(
    q: jnp.ndarray,  # (B, H, hd) single query token
    k_pool: jnp.ndarray,  # (N, KV, block_size, hd) shared block pool
    v_pool: jnp.ndarray,
    mask_pool: jnp.ndarray,  # (N, KV, block_size) per-head slot validity
    table: jnp.ndarray,  # (B, nb) int32 physical block ids (0 = null)
    *,
    pos_pool: jnp.ndarray | None = None,  # (N, KV, block_size) int32
    new_pos: jnp.ndarray | None = None,  # (B,) query-token positions
    window=None,  # None | python int | traced int32 scalar
    depth: int | None = None,  # static logical cache depth (jnp gather path)
    score_masses: bool = False,  # also emit normalized per-row softmax masses
):
    """Decode attention over a paged KV cache (``serving/kv_pool.py``) —
    the serving hot path of ``attention.decode_attention_step_paged``.

    Three dispatch tiers (``paged_decode_path`` names the active one):

    * **kernel** — the Pallas kernel scalar-prefetches the block table
      (plus ``new_pos`` and the window width, which may be *traced*) and
      streams K/V/mask/pos tiles straight from the pool: no dense
      per-sequence copy of the cache exists anywhere on this path.
    * **gather** — jnp dispatch at small depth: gathers the block-table
      view (``ref.gather_paged``, an exact bitwise copy of the pooled
      rows), slices it to ``depth``, and runs the same direct decode
      attention as the dense path — which is what makes paged serving
      bit-identical to dense serving on the jnp dispatch.  This is also
      the test oracle the kernel is checked against.
    * **fallback** — jnp dispatch beyond ``_DIRECT_SEQ`` rows: a
      streaming block scan with the kernel's online-softmax recurrence
      and bounded (B, block_size) temporaries — the memory-traffic shape
      the roofline budget reads (``benchmarks/bench_kernels.py``).

    Dead rows — null blocks behind ragged tables, tails beyond a slot's
    cursor, stale rows of a reallocated block — must be masked False in
    ``mask_pool``; the mask is the single source of validity.  With
    ``window``, rows additionally need ``new_pos - pos < window``.

    With ``score_masses=True`` the return value is ``(out, masses)`` where
    ``masses[b, h, j]`` is the query's normalized softmax probability on
    logical row ``j`` — the decode-time analogue of ``chunk_attention``'s
    fused column masses, streamed into cumulative H2O scores by the
    serving engine's decode-eviction sweep.  ``out`` stays bitwise the
    ``score_masses=False`` result on every tier (the Pallas two-phase
    kernel reruns the identical flash recurrence; the jnp tiers reuse the
    unmodified attention), masked rows carry exact-zero mass, and
    ``masses`` has ``depth`` columns when ``depth`` is given (else
    ``nb * block_size``).
    """
    if use_pallas():
        from repro.kernels import paged_attention as pk

        if score_masses:
            out, masses = pk.paged_decode_masses_pallas(
                q, k_pool, v_pool, mask_pool, table, pos_pool=pos_pool,
                new_pos=new_pos, window=window,
                interpret=_pallas_interpret(),
            )
            return out, (masses if depth is None else masses[..., :depth])
        return pk.paged_decode_attention_pallas(
            q, k_pool, v_pool, mask_pool, table, pos_pool=pos_pool,
            new_pos=new_pos, window=window, interpret=_pallas_interpret(),
        )
    span = table.shape[1] * k_pool.shape[2]
    if depth is not None:
        span = min(span, depth)
    if span <= _DIRECT_SEQ:
        from repro.kernels import ref

        out = ref.paged_decode_attention(
            q, k_pool, v_pool, mask_pool, table, pos_pool=pos_pool,
            new_pos=new_pos, window=window, depth=depth)
        if score_masses:
            masses = ref.paged_decode_masses(
                q, k_pool, mask_pool, table, pos_pool=pos_pool,
                new_pos=new_pos, window=window, depth=depth)
            return out, masses
        return out
    # beyond the direct threshold the dense gather is the O(depth) HBM
    # copy the paged layout exists to avoid; rows past ``depth`` are
    # masked False by construction (appends clamp at depth), so the
    # streaming scan needs no slice
    res = _paged_decode_streaming(
        q, k_pool, v_pool, mask_pool, table, pos_pool=pos_pool,
        new_pos=new_pos, window=window, score_masses=score_masses)
    if score_masses:
        out, masses = res
        return out, (masses if depth is None else masses[..., :depth])
    return res


def paged_decode_path(span: int) -> str:
    """Which ``paged_decode_attention`` tier serves a logical cache of
    ``span`` rows in the current environment: ``"kernel"`` (Pallas),
    ``"gather"`` (jnp direct, the bit-exact oracle) or ``"fallback"``
    (jnp streaming block scan)."""
    if use_pallas():
        return "kernel"
    return "gather" if span <= _DIRECT_SEQ else "fallback"


def _paged_decode_streaming(q, k_pool, v_pool, mask_pool, table, *,
                            pos_pool=None, new_pos=None, window=None,
                            score_masses=False):
    """Gather-free jnp fallback: scan over block-table columns with the
    kernel's online-softmax recurrence — one (B, block_size) K/V tile in
    flight per step, never a dense (B, depth, ...) copy.  With
    ``score_masses`` a second scan revisits each tile with the final
    (m, l) statistics and emits its normalized masses — the streaming
    analogue of the Pallas two-phase kernel, with the same bounded
    temporaries."""
    B, H, hd = q.shape
    KV, bs = k_pool.shape[1], k_pool.shape[2]
    group = H // KV
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    qf = q.astype(jnp.float32)
    cols = jnp.moveaxis(table.astype(jnp.int32), 1, 0)  # (nb, B)

    def rows(pool, tb):  # (B, KV, bs, ...) pool tile -> (B, bs, KV, ...)
        return jnp.swapaxes(pool[tb], 1, 2)

    def tile_logits(tb):
        kb = _expand_gqa(rows(k_pool, tb), group).astype(jnp.float32)
        mb = rows(mask_pool, tb)  # (B, bs, KV)
        if window is not None:
            mb = mb & ((new_pos[:, None, None] - rows(pos_pool, tb))
                       < window)
        s = jnp.einsum("bhd,bkhd->bhk", qf, kb) * scale
        mh = jnp.repeat(jnp.moveaxis(mb, 2, 1), group, axis=1)  # (B, H, bs)
        return jnp.where(mh, s, NEG_INF), mh

    def body(carry, tb):
        m, l, acc = carry
        vb = _expand_gqa(rows(v_pool, tb), group).astype(jnp.float32)
        s, mh = tile_logits(tb)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # the explicit where keeps fully-dead rows at l == 0 (m stays
        # NEG_INF, so exp(s - m) would be exp(0) = 1, not 0)
        p = jnp.where(mh, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bhk,bkhd->bhd", p, vb)
        return (m_new, l, acc), None

    init = (
        jnp.full((B, H), NEG_INF, jnp.float32),
        jnp.zeros((B, H), jnp.float32),
        jnp.zeros((B, H, hd), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, cols)
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    if not score_masses:
        return out
    lsafe = jnp.maximum(l, 1e-30)

    def mass_tile(_, tb):
        s, mh = tile_logits(tb)
        p = jnp.where(mh, jnp.exp(s - m[..., None]), 0.0) / lsafe[..., None]
        return None, p  # (B, H, bs)

    _, tiles = jax.lax.scan(mass_tile, None, cols)  # (nb, B, H, bs)
    nb = cols.shape[0]
    masses = jnp.moveaxis(tiles, 0, 2).reshape(B, H, nb * bs)
    return out, masses


# ---------------------------------------------------------------------------
# lookahead importance scores (the paper's hot spot)
# ---------------------------------------------------------------------------


def lookahead_score(
    q_obs: jnp.ndarray,  # (B, n_obs, H, hd)
    k: jnp.ndarray,  # (B, n_prompt + n_obs, KV, hd)
    n_prompt: int,
    *,
    kv_mask: jnp.ndarray | None = None,
    window=None,  # None | python int | traced int32 scalar
    q_offset=None,  # None | python int | traced int32 scalar
    row_valid: jnp.ndarray | None = None,  # (B, n_obs) real-row mask
    block_k: int = 2048,
) -> jnp.ndarray:
    """Per-q-head importance scores of prompt keys: (B, H, n_prompt), f32.

    Two-pass streaming softmax over the key axis: pass 1 computes per-row max
    and normalizer, pass 2 accumulates normalized probability mass per prompt
    key.  The (n_obs × Sk) score matrix is never materialized in full — only
    (n_obs × block_k) tiles.

    The one masked streaming scoring primitive shared by monolithic and
    chunked prefill: ``q_offset`` may be a *traced* scalar (the Pallas
    kernel prefetches it, so one compiled program serves the deferred
    observation-window scoring at any prompt length), ``window`` restricts
    local layers (static int on the Pallas path; a traced window falls back
    to jnp), and ``row_valid`` zeroes invalid observation rows — they
    contribute exact zeros to the mean, whose denominator stays ``n_obs``.
    """
    B, n_obs, H, hd = q_obs.shape
    Sk = k.shape[1]
    static_window = window is None or isinstance(window, int)
    if use_pallas() and static_window:
        from repro.kernels import lookahead_score as lk

        return lk.lookahead_score_pallas(
            q_obs, k, n_prompt, kv_mask=kv_mask, window=window,
            q_offset=q_offset, row_valid=row_valid,
            block_k=min(block_k, Sk), interpret=_pallas_interpret(),
        )
    if Sk <= _DIRECT_SEQ:
        from repro.kernels import ref

        return ref.lookahead_score(q_obs, k, n_prompt, kv_mask=kv_mask,
                                   window=window, q_offset=q_offset,
                                   row_valid=row_valid)
    return _chunked_lookahead_score(
        q_obs, k, n_prompt, kv_mask=kv_mask, window=window,
        q_offset=q_offset, row_valid=row_valid, block_k=block_k,
    )


def _chunked_lookahead_score(q_obs, k, n_prompt, *, kv_mask, window, q_offset,
                             block_k, row_valid=None, reduce="mean"):
    """Streaming jnp scoring fallback.  ``reduce='mean'`` divides the summed
    per-key mass by n_obs (``lookahead_score`` semantics); ``'sum'`` leaves
    the raw sum over valid rows (``chunk_attention``'s fused-mass
    semantics)."""
    B, n_obs, H, hd = q_obs.shape
    Sk, KV = k.shape[1], k.shape[2]
    group = H // KV
    pad = (-Sk) % block_k
    kf = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    valid = jnp.ones((B, n_prompt), bool) if kv_mask is None else kv_mask
    # full-key validity: prompt mask ++ obs keys valid ++ padding invalid
    full_mask = jnp.concatenate(
        [valid, jnp.ones((B, Sk - n_prompt), bool),
         jnp.zeros((B, pad), bool)], axis=1)
    nk = kf.shape[1] // block_k
    kf = jnp.moveaxis(kf.reshape(B, nk, block_k, KV, hd), 1, 0)
    fm = jnp.moveaxis(full_mask.reshape(B, nk, block_k), 1, 0)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    q32 = q_obs.astype(jnp.float32)
    q_pos = (n_prompt if q_offset is None else q_offset) + jnp.arange(n_obs)

    def tile_logits(ik, kb, mb):
        kbf = _expand_gqa(kb, group).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, kbf) * scale
        k_pos = ik * block_k + jnp.arange(block_k)
        ok = k_pos[None, :] <= q_pos[:, None]  # (n_obs, block_k) causal-on-obs
        if window is not None:
            ok &= (q_pos[:, None] - k_pos[None, :]) < window
        ok = ok[None] & mb[:, None, :]
        return jnp.where(ok[:, None], s, NEG_INF)

    # pass 1: row max + normalizer
    def p1(carry, inputs):
        m, l = carry
        ik, kb, mb = inputs
        s = tile_logits(ik, kb, mb)
        m_new = jnp.maximum(m, s.max(axis=-1))
        l = l * jnp.exp(m - m_new) + jnp.exp(s - m_new[..., None]).sum(-1)
        return (m_new, l), None

    init = (jnp.full((B, H, n_obs), NEG_INF, jnp.float32),
            jnp.zeros((B, H, n_obs), jnp.float32))
    (m, l), _ = jax.lax.scan(p1, init, (jnp.arange(nk), kf, fm))
    l = jnp.maximum(l, 1e-30)
    rv = None
    if row_valid is not None:
        rv = row_valid[:, None, :, None].astype(jnp.float32)  # (B,1,n_obs,1)

    # pass 2: per-key normalized mass, reduced over obs rows
    def p2(_, inputs):
        ik, kb, mb = inputs
        s = tile_logits(ik, kb, mb)
        p = jnp.exp(s - m[..., None]) / l[..., None]
        if rv is not None:
            p = p * rv
        red = p.mean(axis=2) if reduce == "mean" else p.sum(axis=2)
        return None, red  # (B, H, block_k)

    _, tiles = jax.lax.scan(p2, None, (jnp.arange(nk), kf, fm))
    scores = jnp.moveaxis(tiles, 0, 2).reshape(B, H, nk * block_k)
    return scores[..., :n_prompt]


# ---------------------------------------------------------------------------
# Mamba-2 SSD chunked scan
# ---------------------------------------------------------------------------


def ssd_scan(
    x: jnp.ndarray,  # (B, S, nh, hd)
    dt: jnp.ndarray,  # (B, S, nh)
    A: jnp.ndarray,  # (nh,) negative rates
    Bm: jnp.ndarray,  # (B, S, G, ds)
    Cm: jnp.ndarray,  # (B, S, G, ds)
    *,
    chunk: int = 128,
    initial_state: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked state-space-duality scan.  Returns (y, final_state) in f32.

    Within-chunk term is a masked quadratic ("attention-like") form; chunks
    are linked by a sequential state recurrence — O(S·Q) instead of O(S²).
    """
    B, S, nh, hd = x.shape
    if use_pallas() and S % chunk == 0:
        from repro.kernels import ssd_scan as sk

        return sk.ssd_scan_pallas(
            x, dt, A, Bm, Cm, chunk=chunk, initial_state=initial_state,
            interpret=_pallas_interpret(),
        )
    return ssd_scan_chunked_jnp(
        x, dt, A, Bm, Cm, chunk=chunk, initial_state=initial_state
    )


def ssd_scan_chunked_jnp(x, dt, A, Bm, Cm, *, chunk, initial_state=None):
    B, S, nh, hd = x.shape
    G, ds = Bm.shape[2], Bm.shape[3]
    hpg = nh // G
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = x.shape[1]
    nc = Sp // chunk
    x = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    A = A.astype(jnp.float32)
    Bf = jnp.repeat(Bm, hpg, axis=2).astype(jnp.float32)  # (B,Sp,nh,ds)
    Cf = jnp.repeat(Cm, hpg, axis=2).astype(jnp.float32)

    # per-step log decay a_t = A * dt_t  (<= 0)
    a = A[None, None, :] * dt  # (B, Sp, nh)
    xr = jnp.moveaxis(x.reshape(B, nc, chunk, nh, hd), 1, 0)
    dtr = jnp.moveaxis(dt.reshape(B, nc, chunk, nh), 1, 0)
    ar = jnp.moveaxis(a.reshape(B, nc, chunk, nh), 1, 0)
    Br = jnp.moveaxis(Bf.reshape(B, nc, chunk, nh, ds), 1, 0)
    Cr = jnp.moveaxis(Cf.reshape(B, nc, chunk, nh, ds), 1, 0)

    if initial_state is None:
        initial_state = jnp.zeros((B, nh, hd, ds), jnp.float32)
    else:
        initial_state = initial_state.astype(jnp.float32)

    idx = jnp.arange(chunk)
    causal = idx[:, None] >= idx[None, :]  # (t, s): s <= t

    def chunk_step(h, inputs):
        xc, dtc, ac, bc, cc = inputs
        # cumulative decays within the chunk
        L = jnp.cumsum(ac, axis=1)  # (B, Q, nh) — sum_{s<=t} a_s
        # intra-chunk quadratic term:
        #   y_t = sum_{s<=t} (C_t·B_s) exp(L_t - L_s) dt_s x_s
        cb = jnp.einsum("btnd,bsnd->bnts", cc, bc)  # (B, nh, Q, Q)
        decay = jnp.exp(
            jnp.clip(L[:, :, None, :] - L[:, None, :, :], -60.0, 0.0)
        )  # (B, t, s, nh)
        w = cb * jnp.moveaxis(decay, 3, 1) * jnp.where(causal, 1.0, 0.0)[None, None]
        y_intra = jnp.einsum("bnts,bsn,bsnh->btnh", w, dtc, xc)
        # inter-chunk: contribution of the carried state
        y_inter = jnp.einsum(
            "btnd,bnhd,btn->btnh", cc, h, jnp.exp(jnp.clip(L, -60.0, 0.0))
        )
        # state update: h' = exp(L_Q) h + sum_s exp(L_Q - L_s) dt_s x_s ⊗ B_s
        Lq = L[:, -1]  # (B, nh)
        rem = jnp.exp(jnp.clip(Lq[:, None, :] - L, -60.0, 0.0))  # (B, Q, nh)
        dstate = jnp.einsum("bsn,bsn,bsnh,bsnd->bnhd", rem, dtc, xc, bc)
        h = h * jnp.exp(jnp.clip(Lq, -60.0, 0.0))[..., None, None] + dstate
        return h, y_intra + y_inter

    final, ys = jax.lax.scan(chunk_step, initial_state, (xr, dtr, ar, Br, Cr))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, Sp, nh, hd)[:, :S]
    return y, final


def ssd_step(
    x_t: jnp.ndarray,  # (B, nh, hd)
    dt_t: jnp.ndarray,  # (B, nh)
    A: jnp.ndarray,  # (nh,)
    B_t: jnp.ndarray,  # (B, G, ds)
    C_t: jnp.ndarray,  # (B, G, ds)
    state: jnp.ndarray,  # (B, nh, hd, ds)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Single-token SSD recurrence for decode.  Returns (y_t, new_state)."""
    B, nh, hd = x_t.shape
    G = B_t.shape[1]
    hpg = nh // G
    Bf = jnp.repeat(B_t, hpg, axis=1).astype(jnp.float32)
    Cf = jnp.repeat(C_t, hpg, axis=1).astype(jnp.float32)
    x32, dt32 = x_t.astype(jnp.float32), dt_t.astype(jnp.float32)
    decay = jnp.exp(A.astype(jnp.float32)[None] * dt32)  # (B, nh)
    state = state * decay[..., None, None] + (
        (dt32[..., None] * x32)[..., None] * Bf[..., None, :]
    )
    y = jnp.einsum("bnhs,bns->bnh", state, Cf)
    return y.astype(x_t.dtype), state


# ---------------------------------------------------------------------------
# decode attention with exposed online-softmax stats (split-cache decode)
# ---------------------------------------------------------------------------


def decode_attention_stats(
    q: jnp.ndarray,  # (B, H, hd)
    k: jnp.ndarray,  # (B, Sk, KV, hd)
    v: jnp.ndarray,
    *,
    kv_mask: jnp.ndarray | None = None,  # (B, Sk) or (B, Sk, KV)
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Unnormalized flash-decode partials: (m (B,H), l (B,H), acc (B,H,hd)).

    Lets callers attend over *disjoint cache segments with different
    shardings* (frozen seq-sharded prompt cache + replicated hot buffer) and
    merge exactly — the split-cache decode of §Perf (writing into a
    seq-sharded cache otherwise makes XLA all-gather the cache every layer).
    """
    B, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    group = H // KV
    kf = _expand_gqa(k, group).astype(jnp.float32)
    vf = _expand_gqa(v, group).astype(jnp.float32)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), kf) \
        / jnp.sqrt(jnp.float32(hd))
    if kv_mask is not None:
        if kv_mask.ndim == 2:
            ok = kv_mask[:, None, :]
        else:
            ok = jnp.repeat(jnp.moveaxis(kv_mask, 2, 1), group, axis=1)
        s = jnp.where(ok, s, NEG_INF)
        pmask = ok
    else:
        pmask = jnp.ones_like(s, bool)
    m = s.max(axis=-1)
    p = jnp.where(pmask, jnp.exp(s - m[..., None]), 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhk,bkhd->bhd", p, vf)
    return m, l, acc


def merge_attention_stats(parts) -> jnp.ndarray:
    """Combine [(m, l, acc), ...] partials into normalized attention out."""
    m = parts[0][0]
    for mp, _, _ in parts[1:]:
        m = jnp.maximum(m, mp)
    l = 0.0
    acc = 0.0
    for mp, lp, ap in parts:
        corr = jnp.exp(mp - m)
        l = l + lp * corr
        acc = acc + ap * corr[..., None]
    return acc / jnp.maximum(l, 1e-30)[..., None]
