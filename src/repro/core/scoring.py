"""Importance-score computation (paper §2, §3.1).

All scores flow through the same primitive — ``ops.lookahead_score`` — with
different observation queries:

    ground truth   : obs = the true response rows Y          (training target)
    lookaheadkv    : obs = the learned lookahead-token rows  (the paper)
    snapkv         : obs = the last ``window`` prompt rows
    tova           : obs = the last prompt row
    h2o            : obs = every prompt row (cumulative column mass)

Position-based policies (streaming_llm, random, full) don't need attention
at all and are handled in ``eviction.py``.

Score post-processing (paper's standard eviction configuration):
GQA mean-reduction over the query heads of each KV group, then 1-D max-pool
(kernel 7, same padding) along the key axis.

On a windowed layer every score is computed under the layer's window (a
query at position i sees keys j with i - j < window), so keys outside
every observation row's window score exactly zero; eviction then takes
the top-``budget`` as on any layer (``core/eviction.py``).

Streaming (chunked-prefill) scoring
-----------------------------------
``ScoreState`` reformulates every single-pass policy's importance score as
an *online* quantity over prompt chunks (KVpop-style predictive online
pruning), so prefill can stream fixed-size chunks and still evict exactly
like a monolithic pass:

* **cumulative** (h2o): each chunk adds its queries' softmax column masses
  into a running per-key accumulator — a commutative sum, so the final
  scores are chunk-split-invariant.  The per-chunk masses are a *fused
  second output* of the streaming attention pass
  (``ops.chunk_attention(..., score_masses=True)``): the kernel emits them
  tile-by-tile inside its online-softmax recurrence, so no dense (C, K)
  probability block ever materializes on the prefill hot path.
* **observation-window** (snapkv, pyramidkv, tova): only the last
  ``window`` prompt queries matter (1 for tova), so the state is a rolling
  buffer of the newest ``window`` rotary-position-encoded queries; scoring
  defers to the final chunk, where the masked streaming primitive
  ``ops.lookahead_score`` (traced observation base, sliding-window mask)
  scores them over the materialized buffer.
* **final-observation** (lookaheadkv, gt_oracle): the observation rows are
  appended *after* the prompt (learned lookahead rows / the GT response),
  so nothing accumulates during prompt chunks — the observation pass runs
  once at prompt end over the fully materialized key buffer, through the
  same streaming primitive.

The dense (C, K) reference for all of this lives in
``kernels/ref.py::chunk_column_masses`` (test oracle + small-shape direct
path of the ops dispatch).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.ref import NEG_INF

# observation semantics per policy: how many trailing rows act as queries
OBS_POLICIES = ("lookaheadkv", "snapkv", "tova", "h2o", "gt")
POSITION_POLICIES = ("streaming_llm", "random", "full")

# streaming-prefill classification (see module docstring)
STREAMING_CUMULATIVE = ("h2o",)
STREAMING_WINDOW = ("snapkv", "pyramidkv", "tova")
FINAL_OBS = ("lookaheadkv", "gt_oracle")


def observation_scores(
    q_obs: jnp.ndarray,  # (B, n_obs, H, hd)
    k_full: jnp.ndarray,  # (B, n_prompt + n_obs, KV, hd)
    n_prompt: int,
    *,
    window=None,
    kv_mask: jnp.ndarray | None = None,
    q_offset: int | None = None,
) -> jnp.ndarray:
    """Per-q-head scores (B, H, n_prompt), f32 — softmax rows include the
    observation keys (Algorithm 2 slices after the softmax)."""
    return ops.lookahead_score(
        q_obs, k_full, n_prompt, kv_mask=kv_mask, window=window,
        q_offset=q_offset,
    )


def gqa_reduce(scores: jnp.ndarray, num_kv_heads: int) -> jnp.ndarray:
    """(B, H, S) -> (B, KV, S): mean over each KV group's query heads
    (Ada-KV-style GQA compatibility, the paper's default)."""
    B, H, S = scores.shape
    group = H // num_kv_heads
    return scores.reshape(B, num_kv_heads, group, S).mean(axis=2)


def decode_mass_update(
    masses: jnp.ndarray,  # (B, H, D) decode token's normalized softmax masses
    num_kv_heads: int,
    active: Optional[jnp.ndarray] = None,  # (B,) live-slot mask
) -> jnp.ndarray:
    """One decode step's increment to the cumulative (H2O) decode-eviction
    scores: (B, D, KV) f32.

    The paged decode kernel's fused mass output is per *query* head; the
    dense decode-eviction reference (``decode_attention_step_evicting``)
    accumulates ``softmax(...).mean(axis=group)`` per kv head — so the
    increment is the GQA mean transposed into the cache's (row, kv-head)
    layout.  Masked rows arrive as exact zeros from every kernel tier, and
    inactive slots (a zombie decode between requests) are zeroed here so
    their scores stay untouched, mirroring the engine's cursor gating."""
    add = jnp.moveaxis(gqa_reduce(masses, num_kv_heads), 1, 2)  # (B, D, KV)
    if active is not None:
        add = jnp.where(active[:, None, None], add, 0.0)
    return add


def maxpool1d(scores: jnp.ndarray, kernel: int) -> jnp.ndarray:
    """Max-pool along the last axis with 'same' padding (paper kernel=7).

    Clustering effect: keeping a token pulls its neighbourhood along, which
    preserves local context around high-attention spikes.
    """
    if kernel <= 1:
        return scores
    pad = kernel // 2
    x = jnp.pad(scores, [(0, 0)] * (scores.ndim - 1) + [(pad, pad)],
                constant_values=-jnp.inf)
    windows = [x[..., i : i + scores.shape[-1]] for i in range(kernel)]
    return jnp.stack(windows, axis=0).max(axis=0)


def normalize_l1(scores: jnp.ndarray, eps: float = 1e-9) -> jnp.ndarray:
    """L1 normalization ŝ = s / ||s||₁ over the key axis (paper eq. (4))."""
    return scores / jnp.maximum(
        jnp.sum(jnp.abs(scores), axis=-1, keepdims=True), eps
    )


def postprocess(
    scores_per_qhead: jnp.ndarray,  # (B, H, S)
    num_kv_heads: int,
    pool_kernel: int,
) -> jnp.ndarray:
    """Eviction-time pipeline: GQA-reduce then max-pool.  (B, KV, S)."""
    s = gqa_reduce(scores_per_qhead, num_kv_heads)
    return maxpool1d(s, pool_kernel)


# ---------------------------------------------------------------------------
# Streaming scores for chunked prefill
# ---------------------------------------------------------------------------


class ScoreState(NamedTuple):
    """Per-policy streaming score accumulator, threaded across prefill chunks.

    Leaves carry a leading layer axis L (the transformer layer scan slices
    it per layer).  Fields are ``None`` for policies that don't need them —
    the pytree structure is static per compiled (chunk, policy) program.

    ``snapshot``/``restore`` make the state prefix-cacheable: at a chunk
    boundary ``n`` (with every streamed chunk full and the true prompt
    length >= ``n``) the state is a pure function of the first ``n`` prompt
    tokens — chunk updates never read the rows past the boundary, the
    cumulative accumulator is exactly zero there (masked softmax columns
    underflow to +0.0), and per-request randomness (``Request.seed``)
    enters only at finalize (``eviction.position_scores`` folds it in), so
    a snapshot is bit-identical across all requests sharing the prefix.
    """

    acc: Optional[jnp.ndarray] = None   # (L, B, H, K) f32 column-mass sums
    cnt: Optional[jnp.ndarray] = None   # ()  f32 scoring queries seen so far
    qbuf: Optional[jnp.ndarray] = None  # (L, B, W, H, hd) newest W rot. queries

    def snapshot(self, n: int) -> "ScoreState":
        """Capacity-independent snapshot at chunk boundary ``n``: the
        accumulator keeps only its first ``n`` key columns (columns past a
        boundary are exact +0.0 — no query has attended to them), the
        rolling query window and count are boundary state already."""
        if self.acc is None:
            return self
        return self._replace(acc=self.acc[..., :n])

    def restore(self, capacity: int) -> "ScoreState":
        """Re-inflate a snapshot for a ``capacity``-deep key buffer.  The
        zero right-pad reproduces the untouched tail of a freshly streamed
        accumulator bitwise (0.0 + 0.0 stays +0.0 under later adds)."""
        if self.acc is None:
            return self
        pad = capacity - self.acc.shape[-1]
        assert pad >= 0, \
            f"snapshot wider ({self.acc.shape[-1]}) than capacity {capacity}"
        width = [(0, 0)] * (self.acc.ndim - 1) + [(0, pad)]
        return self._replace(acc=jnp.pad(self.acc, width))


def stream_window(policy: str, window_size: int) -> int:
    """Observation-window width a streaming-window policy defers on."""
    return 1 if policy == "tova" else window_size


def init_score_state(
    policy: str,
    num_layers: int,
    batch: int,
    num_heads: int,
    head_dim: int,
    capacity: int,  # key-buffer depth K
    *,
    window_size: int = 32,
    dtype=jnp.float32,
) -> ScoreState:
    """Zero state sized for ``capacity`` buffered keys (policy-shaped)."""
    if policy in STREAMING_CUMULATIVE:
        return ScoreState(
            acc=jnp.zeros((num_layers, batch, num_heads, capacity),
                          jnp.float32),
            cnt=jnp.zeros((), jnp.float32),
        )
    if policy in STREAMING_WINDOW:
        w = stream_window(policy, window_size)
        return ScoreState(
            qbuf=jnp.zeros((num_layers, batch, w, num_heads, head_dim),
                           dtype),
        )
    return ScoreState()  # final-observation and position policies


def update_layer_scores(
    policy: str,
    acc_l: Optional[jnp.ndarray],   # (B, H, K) this layer's accumulator
    qbuf_l: Optional[jnp.ndarray],  # (B, W, H, hd) this layer's query window
    q_rot: jnp.ndarray,  # (B, C, H, hd) the chunk's rotary-encoded queries
    *,
    masses_l: Optional[jnp.ndarray] = None,  # (B, H, K) fused kernel partials
    q_offset: jnp.ndarray,  # scalar int32 chunk start
    n_total: jnp.ndarray,  # scalar int32 true prompt length
) -> tuple[Optional[jnp.ndarray], Optional[jnp.ndarray]]:
    """One chunk's streaming update for one layer; returns (acc', qbuf').

    Cumulative (h2o) policies consume ``masses_l`` — the summed softmax
    column masses of the chunk's valid rows, emitted by the attention
    kernel itself (``ops.chunk_attention(..., score_masses=True)``) — so
    the update is a plain accumulator add; no score matrix is recomputed
    or materialized here."""
    C = q_rot.shape[1]
    if policy in STREAMING_CUMULATIVE:
        assert masses_l is not None, \
            f"{policy} needs the attention kernel's fused mass output"
        return acc_l + masses_l, qbuf_l
    if policy in STREAMING_WINDOW:
        # roll the newest W *valid* rows in: global rows [total-W, total)
        # where total = min(n_total, chunk end).  Early chunks shorter than
        # W leave stale low slots that later chunks displace before any read.
        W = qbuf_l.shape[1]
        total = jnp.minimum(n_total, q_offset + C)
        joined = jnp.concatenate([qbuf_l, q_rot], axis=1)  # (B, W + C, H, hd)
        start = jnp.clip(total - q_offset, 0, C)  # joined idx of row total-W
        qbuf_l = jax.lax.dynamic_slice_in_dim(joined, start, W, axis=1)
        return acc_l, qbuf_l
    return acc_l, qbuf_l


def finalize_layer_scores(
    policy: str,
    k_buf: jnp.ndarray,  # (B, K, KV, hd)
    n_total: jnp.ndarray,  # scalar int32 true prompt length
    *,
    acc_l: Optional[jnp.ndarray] = None,
    cnt: Optional[jnp.ndarray] = None,
    qbuf_l: Optional[jnp.ndarray] = None,
    obs_masses_l: Optional[jnp.ndarray] = None,  # (B, H, K) mean obs masses
    num_kv_heads: int,
    pool_kernel: int,
    window_size: int = 32,
    window=None,
    smesh=None,  # model_shard_mesh-vetted mesh: per-shard head scoring
) -> jnp.ndarray:
    """Eviction-ready scores (B, KV, K) at prompt end, mirroring the
    monolithic pipeline exactly: GQA-reduce, max-pool over the *scored*
    region only (columns past the policy's boundary are -inf, matching the
    monolithic maxpool's edge padding), then the snapkv-family force-keep
    boost, then the valid-key mask.  Columns >= ``n_total`` rank last and
    are additionally masked out of the cache by ``evict_layer``'s
    ``key_mask``."""
    B, K, KV, _ = k_buf.shape
    col = jnp.arange(K)
    if policy in STREAMING_CUMULATIVE:
        s_qh = acc_l / jnp.maximum(cnt, 1.0)
        boundary = n_total
    elif policy in STREAMING_WINDOW:
        W = stream_window(policy, window_size)
        boundary = n_total - W
        # the masked streaming primitive scores the rolled window queries
        # over the whole buffer (traced observation base ``boundary``);
        # mean over the W rows == the monolithic sum / W
        from repro.models.attention import sharded_lookahead_score

        s_qh = sharded_lookahead_score(
            qbuf_l, k_buf, K, q_offset=boundary, window=window, smesh=smesh,
        )
    else:  # final-observation policies
        assert obs_masses_l is not None, f"{policy} needs an observation pass"
        s_qh = obs_masses_l
        boundary = n_total
    s_kv = gqa_reduce(s_qh, num_kv_heads)
    s_kv = jnp.where(col[None, None, :] < boundary, s_kv, -jnp.inf)
    s_kv = maxpool1d(s_kv, pool_kernel)
    if policy in STREAMING_WINDOW:
        # monolithic path: scores past the boundary are zero-padded, then the
        # observation window is force-kept — exactly 1e9 per window column
        in_window = (col[None, None, :] >= boundary) & \
            (col[None, None, :] < n_total)
        s_kv = jnp.where(in_window, 1e9, s_kv)
    return jnp.where(col[None, None, :] < n_total, s_kv, NEG_INF)
