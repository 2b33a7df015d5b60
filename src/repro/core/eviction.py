"""Eviction: Top-K selection + per-KV-head gather into a budgeted cache.

The per-layer entry point is ``evict_layer`` — called from inside the
prefill layer scan with that layer's (q, k, v) and the policy's scores.
Shapes are static: every layer emits a cache of ``capacity`` slots; a
validity mask implements per-layer budgets (PyramidKV) and padding.

Position-based policies (StreamingLLM sink+recent, random, full) are
expressed as synthetic score vectors so that one TopK path serves all
policies — this also makes the "budget is always respected" property test
uniform across policies.

Windowed layers (a sliding layer of a local:global model) follow the same
rule as full ones, with the window applied where attention is computed:
the layer keeps, per (layer, kv head), the top-``budget`` prompt keys of
its pooled lookahead scores, those scores being the observation rows'
softmax mass under the layer's window (``core/scoring.py``: keys a row's
window does not show get exactly zero mass); decode then reads the kept
rows only inside the window, by their original position
(``new_pos - pos < window``).  At budget = window (Mellum2: 1,024) the
top-``budget`` is drawn from the window's last ``window - 1`` prompt keys
and the max-pool's ``pool // 2`` spill keys past its edge, so a sliding
layer keeps all but a few of the keys its window shows.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class EvictedKV(NamedTuple):
    k: jnp.ndarray  # (B, capacity, KV, hd)
    v: jnp.ndarray  # (B, capacity, KV, hd)
    pos: jnp.ndarray  # (B, capacity, KV) original token positions, int32
    mask: jnp.ndarray  # (B, capacity, KV) slot validity


def position_scores(
    policy: str,
    n_prompt: int,
    batch: int,
    num_kv_heads: int,
    *,
    sink: int = 4,
    budget: int = 0,
    seed: int = 0,
    seeds: Optional[jnp.ndarray] = None,  # (B,) per-request seeds, int32
) -> jnp.ndarray:
    """Synthetic (B, KV, n_prompt) scores for attention-free policies.

    ``seeds`` decorrelates the ``random`` policy across requests: row ``b``
    draws from ``fold_in(PRNGKey(seed), seeds[b])``.  Every ``random`` draw
    is additionally folded per *position*, so the value at position p is
    independent of the score-vector length — chunked prefill (which scores
    over its full buffer depth) and monolithic prefill (which scores over
    exactly ``n_prompt`` columns) agree on every shared position, seeded or
    not.  Without ``seeds`` every row of every batch shares one
    ``PRNGKey(seed)`` stream — fine for single-request experiments, but a
    batch of requests would all evict the *same* "random" positions.
    """
    pos = jnp.arange(n_prompt, dtype=jnp.float32)
    if policy == "streaming_llm":
        recent = pos  # larger position => more recent => higher
        sink_boost = jnp.where(pos < sink, 1e9, 0.0)
        s = recent + sink_boost
    elif policy == "full":
        s = jnp.full((n_prompt,), 1.0)
    elif policy == "random":
        base = jax.random.PRNGKey(seed)

        def row(kr):
            return jax.vmap(
                lambda p: jax.random.uniform(jax.random.fold_in(kr, p))
            )(jnp.arange(n_prompt))

        if seeds is not None:
            sb = jax.vmap(
                lambda rs: row(jax.random.fold_in(base, rs))
            )(seeds.astype(jnp.uint32))  # (B, n_prompt)
            return jnp.broadcast_to(
                sb[:, None, :], (batch, num_kv_heads, n_prompt))
        s = row(base)
    else:
        raise ValueError(f"not a position policy: {policy}")
    return jnp.broadcast_to(s[None, None, :], (batch, num_kv_heads, n_prompt))


def keep_window(scores: jnp.ndarray, window: int) -> jnp.ndarray:
    """Force-keep the last ``window`` prompt tokens (SnapKV convention)."""
    n = scores.shape[-1]
    boost = jnp.where(jnp.arange(n) >= n - window, 1e9, 0.0)
    return scores + boost[None, None, :]


def select_topk(
    scores: jnp.ndarray,  # (B, KV, n_prompt) post-processed scores
    capacity: int,
    *,
    layer_budget: Optional[jnp.ndarray] = None,  # traced scalar <= capacity
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-``capacity`` indices per (batch, kv head), sorted by position.

    Returns (idx (B, KV, capacity) int32, mask (B, KV, capacity) bool).
    ``layer_budget`` (PyramidKV) invalidates slots beyond the layer's budget
    while keeping shapes static for the layer scan.
    """
    n = scores.shape[-1]
    cap = min(capacity, n)
    _, idx = jax.lax.top_k(scores, cap)  # (B, KV, cap) by score desc
    mask = jnp.ones(idx.shape, bool)
    if layer_budget is not None:
        mask &= jnp.arange(cap)[None, None, :] < layer_budget
    if cap < capacity:  # pad static shape
        pad = capacity - cap
        idx = jnp.pad(idx, ((0, 0), (0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, pad)))
    # restore temporal order (keeps positional structure of the cache)
    order = jnp.argsort(jnp.where(mask, idx, jnp.iinfo(jnp.int32).max), axis=-1)
    idx = jnp.take_along_axis(idx, order, axis=-1)
    mask = jnp.take_along_axis(mask, order, axis=-1)
    return idx.astype(jnp.int32), mask


def gather_kv(
    k: jnp.ndarray,  # (B, S, KV, hd)
    v: jnp.ndarray,
    idx: jnp.ndarray,  # (B, KV, capacity)
    mask: jnp.ndarray,  # (B, KV, capacity)
) -> EvictedKV:
    """Per-kv-head gather of the retained slots.

    Each kept slot moves as one ``head_dim``-wide row: the gather indexes
    only the row axis of each (batch, kv head), so XLA emits one index per
    row rather than one per element (``select_topk``'s indices are always
    in range)."""

    def rows(x, i):  # (S, hd), (cap,) -> (cap, hd)
        return jnp.take(x, i, axis=0, mode="clip")

    take = jax.vmap(jax.vmap(rows, in_axes=(1, 0), out_axes=1))
    kk, vv = take(k, idx), take(v, idx)
    pos = jnp.swapaxes(idx, 1, 2)  # (B, cap, KV)
    m = jnp.swapaxes(mask, 1, 2)
    kk = jnp.where(m[..., None], kk, 0)
    vv = jnp.where(m[..., None], vv, 0)
    return EvictedKV(k=kk, v=vv, pos=pos, mask=m)


def evict_layer(
    scores: jnp.ndarray,  # (B, KV, n_prompt)
    k: jnp.ndarray,  # (B, n_prompt, KV, hd) prompt keys only
    v: jnp.ndarray,
    capacity: int,
    *,
    layer_budget: Optional[jnp.ndarray] = None,
    head_budgets: Optional[jnp.ndarray] = None,  # (B, KV) Ada-KV allocation
    extra_slots: int = 0,
    key_mask: Optional[jnp.ndarray] = None,  # (B, n_prompt) valid prompt keys
) -> EvictedKV:
    """Evict one layer's prompt KV down to ``capacity`` kept slots, with
    ``extra_slots`` empty tail capacity for subsequent decode appends.

    ``key_mask`` marks which prompt keys are real (bucketed serving pads
    prompts to a common length): padded keys may still be *selected* when
    capacity exceeds the true prompt length, but their cache slots come out
    masked invalid, so decode never attends to them.
    """
    if head_budgets is not None:
        idx, mask = select_topk_per_head(scores, capacity, head_budgets)
    else:
        idx, mask = select_topk(scores, capacity, layer_budget=layer_budget)
    if key_mask is not None:
        B, KV, cap = idx.shape
        valid = jnp.broadcast_to(key_mask[:, None, :], (B, KV, key_mask.shape[-1]))
        mask &= jnp.take_along_axis(valid, idx, axis=-1)
    ev = gather_kv(k, v, idx, mask)
    if extra_slots:
        B, _, KV, hd = k.shape

        def padkv(x):
            return jnp.pad(x, ((0, 0), (0, extra_slots), (0, 0), (0, 0)))

        ev = EvictedKV(
            k=padkv(ev.k),
            v=padkv(ev.v),
            pos=jnp.pad(ev.pos, ((0, 0), (0, extra_slots), (0, 0))),
            mask=jnp.pad(ev.mask, ((0, 0), (0, extra_slots), (0, 0))),
        )
    return ev


def pyramid_budgets(num_layers: int, budget: int, beta: float) -> jnp.ndarray:
    """PyramidKV-style funnel: linearly decaying per-layer budgets whose mean
    equals ``budget``.  First layer gets ~2β/(β+1)× budget, last ~2/(β+1)×."""
    hi = 2.0 * beta / (beta + 1.0) * budget
    lo = 2.0 / (beta + 1.0) * budget
    b = jnp.linspace(hi, lo, num_layers)
    return jnp.maximum(b.astype(jnp.int32), 1)


def uniform_budgets(num_layers: int, budget: int) -> jnp.ndarray:
    return jnp.full((num_layers,), budget, jnp.int32)


def adaptive_head_budgets(
    scores: jnp.ndarray,  # (B, KV, n) post-processed scores
    total_budget: int,  # per-head budget × KV = the global pool
    capacity: int,  # static per-head slot count (>= any allocated budget)
    *,
    floor: int = 4,
) -> jnp.ndarray:
    """Ada-KV-style adaptive budget allocation (Feng et al. 2024 — cited by
    the paper as an orthogonal improvement; implemented here as a composable
    policy axis).

    Instead of giving every kv head the same budget, distribute the global
    pool ``KV · total_budget`` in proportion to each head's top-score mass —
    flat heads (mass spread thin) give slots to spiky heads (mass
    concentrated on few keys), subject to a per-head floor and the static
    ``capacity`` ceiling.  Returns int32 budgets (B, KV) summing to
    ≈ KV · total_budget.
    """
    B, KV, n = scores.shape
    pool = KV * total_budget
    k = min(total_budget, n)
    top_mass, _ = jax.lax.top_k(scores, k)  # (B, KV, k)
    mass = top_mass.sum(-1)
    frac = mass / jnp.maximum(mass.sum(axis=1, keepdims=True), 1e-9)
    raw = frac * pool
    b = jnp.clip(raw.astype(jnp.int32), floor, capacity)
    # water-filling: mass stranded by the floor/ceiling clips redistributes
    # equally among heads that still have room (3 rounds suffice for KV<=64)
    for _ in range(3):
        deficit = jnp.maximum(pool - b.sum(axis=1, keepdims=True), 0)
        room = capacity - b
        nroom = jnp.maximum((room > 0).sum(axis=1, keepdims=True), 1)
        give = jnp.minimum(room, deficit // nroom)
        b = b + give
    # final ±1 remainder onto the highest-mass heads with room
    leftover = jnp.maximum(pool - b.sum(axis=1, keepdims=True), 0)
    order = jnp.argsort(-jnp.where(b < capacity, raw, -jnp.inf), axis=1)
    bonus = (jnp.arange(KV)[None, :] < leftover).astype(jnp.int32)
    bonus = jnp.take_along_axis(bonus, jnp.argsort(order, axis=1), axis=1)
    return jnp.clip(b + bonus, floor, capacity)


def select_topk_per_head(
    scores: jnp.ndarray,  # (B, KV, n)
    capacity: int,
    head_budgets: jnp.ndarray,  # (B, KV) int32, <= capacity
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-``capacity`` slots with per-(batch, head) *budget* masks — the
    adaptive-allocation companion to ``select_topk`` (same static shapes)."""
    n = scores.shape[-1]
    cap = min(capacity, n)
    _, idx = jax.lax.top_k(scores, cap)
    mask = jnp.arange(cap)[None, None, :] < head_budgets[..., None]
    if cap < capacity:
        pad = capacity - cap
        idx = jnp.pad(idx, ((0, 0), (0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, pad)))
    order = jnp.argsort(jnp.where(mask, idx, jnp.iinfo(jnp.int32).max), axis=-1)
    idx = jnp.take_along_axis(idx, order, axis=-1)
    mask = jnp.take_along_axis(mask, order, axis=-1)
    return idx.astype(jnp.int32), mask
