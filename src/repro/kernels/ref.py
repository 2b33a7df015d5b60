"""Pure-jnp oracles for every Pallas kernel in this package.

These are deliberately naive (materialize full score matrices, sequential
scans) — they define numerical ground truth for the kernel allclose sweeps in
``tests/test_kernels_*.py`` and for small-scale CPU execution.

Shared conventions
------------------
q:  (B, Sq, H, hd)       queries
k:  (B, Sk, KV, hd)      keys   (GQA: H = KV * G)
v:  (B, Sk, KV, hd)      values
kv_mask: (B, Sk) bool    validity of each cache slot (True = attend)
positions are absolute; causal masking compares absolute positions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _expand_gqa(x: jnp.ndarray, group: int) -> jnp.ndarray:
    """(B, S, KV, hd) -> (B, S, KV*G, hd) by repeating each kv head."""
    return jnp.repeat(x, group, axis=2)


def _logits_mask(
    q_pos: jnp.ndarray,  # (B, Sq)
    k_pos: jnp.ndarray,  # (B, Sk)
    *,
    causal: bool,
    window,  # None = unbounded; python int or traced int32 scalar otherwise
    kv_mask: jnp.ndarray | None,
) -> jnp.ndarray:
    """(B, Sq, Sk) bool: True where attention is allowed."""
    ok = jnp.ones((q_pos.shape[0], q_pos.shape[1], k_pos.shape[1]), bool)
    if causal:
        ok &= k_pos[:, None, :] <= q_pos[:, :, None]
    if window is not None:
        ok &= (q_pos[:, :, None] - k_pos[:, None, :]) < window
    if kv_mask is not None:
        ok &= kv_mask[:, None, :]
    return ok


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window=None,
    q_pos: jnp.ndarray | None = None,
    k_pos: jnp.ndarray | None = None,
    kv_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Naive masked softmax attention.  Returns (B, Sq, H, hd)."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    group = H // KV
    if q_pos is None:
        q_pos = jnp.broadcast_to(jnp.arange(Sk - Sq, Sk), (B, Sq))
    if k_pos is None:
        k_pos = jnp.broadcast_to(jnp.arange(Sk), (B, Sk))
    kf = _expand_gqa(k, group)
    vf = _expand_gqa(v, group)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), kf.astype(jnp.float32)
    ) / jnp.sqrt(hd).astype(jnp.float32)
    ok = _logits_mask(q_pos, k_pos, causal=causal, window=window, kv_mask=kv_mask)
    logits = jnp.where(ok[:, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vf.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,  # (B, H, hd) single query token
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    kv_mask: jnp.ndarray | None = None,  # (B, Sk) or (B, Sk, KV) per-head
) -> jnp.ndarray:
    B, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    group = H // KV
    kf = _expand_gqa(k, group)
    vf = _expand_gqa(v, group)
    logits = jnp.einsum(
        "bhd,bkhd->bhk", q.astype(jnp.float32), kf.astype(jnp.float32)
    ) / jnp.sqrt(hd).astype(jnp.float32)
    if kv_mask is not None:
        if kv_mask.ndim == 2:
            ok = kv_mask[:, None, :]  # (B, 1, Sk)
        else:  # (B, Sk, KV) -> (B, H, Sk)
            ok = jnp.repeat(jnp.moveaxis(kv_mask, 2, 1), group, axis=1)
        logits = jnp.where(ok, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", probs, vf.astype(jnp.float32))
    return out.astype(q.dtype)


def lookahead_score(
    q_obs: jnp.ndarray,  # (B, n_obs, H, hd) — queries of the observation rows
    k: jnp.ndarray,  # (B, n_prompt + n_obs, KV, hd) — prompt keys then obs keys
    n_prompt: int,
    *,
    kv_mask: jnp.ndarray | None = None,  # (B, n_prompt) prompt-key validity
    window=None,  # sliding-window span for local layers (None = full)
    q_offset: int | None = None,  # absolute position of obs row 0 (default n_prompt)
    row_valid: jnp.ndarray | None = None,  # (B, n_obs) real-row mask
) -> jnp.ndarray:
    """Ground-truth importance scores (paper eq. (1)/(3)).

    The observation rows sit causally *after* the prompt: obs row i attends to
    all prompt keys plus obs keys j <= i.  The softmax normalizer therefore
    includes the obs-to-obs mass (Algorithm 2 slices A[n_in:, :n_in] *after*
    the softmax).  Returns per-q-head scores, mean over obs rows:
    (B, H, n_prompt), f32.

    ``row_valid`` marks real observation rows: invalid (padded / beyond the
    true prompt length) rows contribute exact zeros to the mean, whose
    denominator stays ``n_obs`` — callers that want a sum over valid rows
    rescale by ``n_obs``.
    """
    B, n_obs, H, hd = q_obs.shape
    Sk = k.shape[1]
    KV = k.shape[2]
    group = H // KV
    kf = _expand_gqa(k, group)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q_obs.astype(jnp.float32), kf.astype(jnp.float32)
    ) / jnp.sqrt(hd).astype(jnp.float32)
    # causal among obs rows; all prompt keys visible.
    q_pos = (n_prompt if q_offset is None else q_offset) + jnp.arange(n_obs)
    k_pos = jnp.arange(Sk)
    ok = k_pos[None, :] <= q_pos[:, None]  # (n_obs, Sk)
    if window is not None:
        ok &= (q_pos[:, None] - k_pos[None, :]) < window
    ok = jnp.broadcast_to(ok[None], (B, n_obs, Sk))
    if kv_mask is not None:
        full_mask = jnp.concatenate(
            [kv_mask, jnp.ones((B, Sk - n_prompt), bool)], axis=1
        )
        ok &= full_mask[:, None, :]
    logits = jnp.where(ok[:, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)  # (B, H, n_obs, Sk)
    if row_valid is not None:
        probs = probs * row_valid[:, None, :, None].astype(jnp.float32)
    scores = probs[..., :n_prompt].mean(axis=2)  # (B, H, n_prompt)
    return scores


def chunk_column_masses(
    q: jnp.ndarray,  # (B, C, H, hd) rotary-encoded chunk queries
    k: jnp.ndarray,  # (B, K, KV, hd) key buffer; col j holds position j
    *,
    q_offset,  # scalar int32 (may be traced) — absolute position of q row 0
    window=None,
    row_valid: jnp.ndarray | None = None,  # (B, C) real-row mask
) -> jnp.ndarray:
    """Summed softmax column masses of the chunk's queries: (B, H, K) f32.

    The dense oracle for the fused score output of
    ``chunk_attention.chunk_attention_masses_pallas`` and the streaming jnp
    fallback in ``ops.chunk_attention`` — it materializes the full
    (B, H, C, K) probability block, so it is test-/small-shape-only.  The
    per-row softmax is the same computation as ``lookahead_score`` (causal
    on absolute positions, NEG_INF masking, f32) — buffer columns a row
    cannot see contribute *exact zeros*, so streaming accumulation over
    chunks reproduces the monolithic scores up to summation order.  Rows
    beyond the true prompt length are zeroed via ``row_valid`` before the
    sum.
    """
    B, C, H, hd = q.shape
    K, KV = k.shape[1], k.shape[2]
    kf = _expand_gqa(k, H // KV)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), kf.astype(jnp.float32)
    ) / jnp.sqrt(hd).astype(jnp.float32)
    q_pos = q_offset + jnp.arange(C)
    k_pos = jnp.arange(K)
    ok = k_pos[None, :] <= q_pos[:, None]  # (C, K)
    if window is not None:
        ok &= (q_pos[:, None] - k_pos[None, :]) < window
    logits = jnp.where(ok[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)  # (B, H, C, K)
    if row_valid is not None:
        probs = probs * row_valid[:, None, :, None].astype(jnp.float32)
    return probs.sum(axis=2)


def ssd_scan(
    x: jnp.ndarray,  # (B, S, nh, hd) — pre-discretization inputs
    dt: jnp.ndarray,  # (B, S, nh)    — softplus'd timestep
    A: jnp.ndarray,  # (nh,)          — negative decay rates (A = -exp(A_log))
    Bm: jnp.ndarray,  # (B, S, G, ds)
    Cm: jnp.ndarray,  # (B, S, G, ds)
    *,
    initial_state: jnp.ndarray | None = None,  # (B, nh, hd, ds)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential Mamba-2 SSD recurrence oracle.

    h_t = exp(A * dt_t) * h_{t-1} + dt_t * x_t ⊗ B_t
    y_t = h_t · C_t

    Returns (y: (B, S, nh, hd), final_state: (B, nh, hd, ds)), f32.
    """
    B, S, nh, hd = x.shape
    G, ds = Bm.shape[2], Bm.shape[3]
    heads_per_group = nh // G
    Bm = jnp.repeat(Bm, heads_per_group, axis=2)  # (B,S,nh,ds)
    Cm = jnp.repeat(Cm, heads_per_group, axis=2)
    x, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bm, Cm = Bm.astype(jnp.float32), Cm.astype(jnp.float32)
    A = A.astype(jnp.float32)
    if initial_state is None:
        initial_state = jnp.zeros((B, nh, hd, ds), jnp.float32)

    def step(h, inputs):
        xt, dtt, bt, ct = inputs  # (B,nh,hd), (B,nh), (B,nh,ds), (B,nh,ds)
        decay = jnp.exp(A[None] * dtt)  # (B, nh)
        h = h * decay[..., None, None] + (
            (dtt[..., None] * xt)[..., None] * bt[..., None, :]
        )
        y = jnp.einsum("bnhs,bns->bnh", h, ct)
        return h, y

    xs = (
        jnp.moveaxis(x, 1, 0),
        jnp.moveaxis(dt, 1, 0),
        jnp.moveaxis(Bm, 1, 0),
        jnp.moveaxis(Cm, 1, 0),
    )
    final, ys = jax.lax.scan(step, initial_state, xs)
    return jnp.moveaxis(ys, 0, 1), final


def gather_paged(pool: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Flatten a block-table view of a paged pool into the dense cache
    layout: pool (N, KV, bs, ...) + table (B, nb) -> (B, nb*bs, KV, ...),
    where logical row ``c`` of sequence ``b``, kv head ``h`` is
    ``pool[table[b, c // bs], h, c % bs]``.  Gathers are exact — the dense
    view is a bitwise copy of the pooled rows (the paged-vs-dense
    equivalence lemma)."""
    B, nb = table.shape
    g = jnp.swapaxes(pool[table], 2, 3)  # (B, nb, bs, KV, ...)
    return g.reshape((B, nb * pool.shape[2], pool.shape[1]) + pool.shape[3:])


def paged_decode_attention(
    q: jnp.ndarray,  # (B, H, hd) single query token
    k_pool: jnp.ndarray,  # (N, KV, block_size, hd) shared block pool
    v_pool: jnp.ndarray,
    mask_pool: jnp.ndarray,  # (N, KV, block_size) per-head validity
    table: jnp.ndarray,  # (B, nb) int32 physical block ids (0 = null)
    *,
    pos_pool: jnp.ndarray | None = None,  # (N, KV, block_size) int32 positions
    new_pos: jnp.ndarray | None = None,  # (B,) query-token absolute positions
    window=None,  # None | python int | traced int32 scalar
    depth: int | None = None,  # static logical depth: slice the gathered view
) -> jnp.ndarray:
    """Dense oracle for the paged decode kernel: materialize the
    block-table gather and run the naive masked decode attention over it.
    Dead rows (null blocks, ragged tails, stale previous owners) must be
    masked False in ``mask_pool`` — the mask is the sole validity source,
    as in the dense cache layout.

    ``window`` applies the dense path's sliding-window predicate
    ``new_pos - pos < window`` on the gathered ``pos_pool`` rows; with
    ``depth`` the gathered view is sliced to the dense engine's logical
    cache depth before attending, which makes this oracle *bitwise* the
    old gather-hop serving step (same reduction order as the dense cache).

    A sequence/head with *no* attendable key anywhere (an all-null table
    — a slot between requests — or every in-window row masked) is defined
    to be exact zeros, matching the flash kernels' ``l -> max(l, eps)``
    convention rather than the naive softmax's uniform-over-garbage
    limit."""
    mask = gather_paged(mask_pool, table)  # (B, S, KV)
    k = gather_paged(k_pool, table)
    v = gather_paged(v_pool, table)
    if depth is not None:
        k, v, mask = k[:, :depth], v[:, :depth], mask[:, :depth]
    if window is not None:
        assert pos_pool is not None and new_pos is not None, \
            "sliding-window masking needs pos_pool and new_pos"
        pos = gather_paged(pos_pool, table)  # (B, S, KV)
        if depth is not None:
            pos = pos[:, :depth]
        mask = mask & ((new_pos[:, None, None] - pos) < window)
    out = decode_attention(q, k, v, kv_mask=mask)
    B, H, _ = q.shape
    KV = mask_pool.shape[1]
    alive = jnp.repeat(mask.any(axis=1), H // KV, axis=1)  # (B, H)
    return jnp.where(alive[..., None], out, 0.0).astype(out.dtype)


def paged_decode_masses(
    q: jnp.ndarray,  # (B, H, hd) single query token
    k_pool: jnp.ndarray,  # (N, KV, block_size, hd) shared block pool
    mask_pool: jnp.ndarray,  # (N, KV, block_size) per-head validity
    table: jnp.ndarray,  # (B, nb) int32 physical block ids (0 = null)
    *,
    pos_pool: jnp.ndarray | None = None,
    new_pos: jnp.ndarray | None = None,
    window=None,
    depth: int | None = None,
) -> jnp.ndarray:
    """Dense oracle for the decode token's per-key softmax masses over a
    paged cache: (B, H, S) f32, S = nb*block_size (or ``depth``).

    Row j holds the normalized probability the query puts on logical cache
    row j — the decode-time analogue of ``chunk_column_masses``, streamed
    into cumulative H2O scores by the serving engine's decode-eviction
    sweep.  Masked rows contribute *exact zeros* and a sequence/head with
    no attendable row is all-zero (``l -> max(l, eps)``), matching the
    flash kernels — so accumulating masses over steps reproduces the dense
    ``decode_attention_step_evicting`` score recurrence, which adds
    ``where(mask, probs, 0)`` each step."""
    mask = gather_paged(mask_pool, table)  # (B, S, KV)
    k = gather_paged(k_pool, table)
    if depth is not None:
        k, mask = k[:, :depth], mask[:, :depth]
    if window is not None:
        assert pos_pool is not None and new_pos is not None, \
            "sliding-window masking needs pos_pool and new_pos"
        pos = gather_paged(pos_pool, table)
        if depth is not None:
            pos = pos[:, :depth]
        mask = mask & ((new_pos[:, None, None] - pos) < window)
    B, H, hd = q.shape
    KV = k.shape[2]
    group = H // KV
    kf = _expand_gqa(k, group)
    logits = jnp.einsum(
        "bhd,bkhd->bhk", q.astype(jnp.float32), kf.astype(jnp.float32)
    ) / jnp.sqrt(hd).astype(jnp.float32)
    ok = jnp.repeat(jnp.moveaxis(mask, 2, 1), group, axis=1)  # (B, H, S)
    logits = jnp.where(ok, logits, NEG_INF)
    m = logits.max(axis=-1, keepdims=True)
    p = jnp.where(ok, jnp.exp(logits - m), 0.0)
    l = jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    return p / l
