"""Unified decoder(-encoder) stack covering all ten assigned architectures.

One parameter tree + three entry points:

* ``prefill``      — full-sequence forward with optional lookahead rows,
                     per-layer importance scoring, and in-scan KV eviction.
                     Used for the LookaheadKV training passes (GT pass and
                     lookahead pass), plain LM training, and the deprecated
                     bucketed serving path.
* ``prefill_chunk`` / ``prefill_finalize`` — streaming prefill: fixed
                     (B, chunk) blocks with online score accumulation, one
                     eviction at prompt end.  The serving prefill path.
* ``decode_step``  — single-token step against the (possibly evicted) cache.
* ``encode``       — whisper bidirectional encoder over stub frame embeddings.

Per-layer parameters are stacked along a leading ``L`` axis and the depth
runs under ``jax.lax.scan`` — HLO size is O(1) in depth, which keeps the
512-device dry-run compiles tractable (DESIGN.md §4).

Block composition by arch type:
    dense / vlm / moe : h += attn(ln1(h));            h += ffn|moe(ln2(h))
    ssm (mamba2)      : h += ssd(ln1(h))              (no FFN when d_ff == 0)
    hybrid (hymba)    : h += ½·(attn(u) + ssd(u)),  u = ln1(h);  h += ffn(ln2(h))
    audio (whisper)   : encoder blocks (bidir attn + ffn);
                        decoder blocks (self-attn + cross-attn + ffn)
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import numpy as np

from repro.common.config import EvictionConfig, ModelConfig
from repro.core import eviction as ev
from repro.core import scoring
from repro.core.lookahead import append_lookahead, lora_scale
from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import AttnInputs, layer_window
from repro.models.layers import dense_init, embed_init, rms_norm
from repro.models.rope import text_mrope_positions

# Policies that derive scores from observation-row attention.
OBS_POLICIES = ("lookaheadkv", "gt_oracle", "snapkv", "pyramidkv", "tova", "h2o")
POSITION_POLICIES = ("full", "random", "streaming_llm")


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _zeros(shape, dtype):
    return jnp.zeros(shape, dtype)


def _init_layer(key, cfg: ModelConfig, *, with_cross: bool = False) -> dict:
    ks = jax.random.split(key, 6)
    dtype = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    p: dict = {"ln1": _zeros((d,), dtype)}
    if cfg.uses_attention:
        p["attn"] = attn_mod.init(ks[0], cfg)
    if cfg.uses_ssm:
        p["ssm"] = ssm_mod.init(ks[1], cfg)
    if with_cross:
        p["cross"] = attn_mod.init(ks[2], cfg, cross=True)
        p["ln_cross"] = _zeros((d,), dtype)
    if cfg.moe is not None:
        p["moe"] = moe_mod.init(ks[3], cfg)
        p["ln2"] = _zeros((d,), dtype)
    elif cfg.d_ff > 0:
        p["mlp"] = mlp_mod.init(ks[4], cfg)
        p["ln2"] = _zeros((d,), dtype)
    return p


def _init_encoder_layer(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 2)
    dtype = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    return {
        "ln1": _zeros((d,), dtype),
        "attn": attn_mod.init(ks[0], cfg),
        "ln2": _zeros((d,), dtype),
        "mlp": mlp_mod.init(ks[1], cfg),
    }


def init_params(key, cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    k_emb, k_layers, k_enc, k_head = jax.random.split(key, 4)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    with_cross = cfg.is_encoder_decoder
    layers = jax.vmap(
        lambda k: _init_layer(k, cfg, with_cross=with_cross)
    )(layer_keys)
    params = {
        "embed": embed_init(k_emb, cfg.padded_vocab, cfg.d_model, dtype),
        "layers": layers,
        "final_norm": _zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, cfg.d_model,
                                       cfg.padded_vocab, dtype)
    if cfg.is_encoder_decoder:
        ek = jax.random.split(k_enc, cfg.encoder.num_layers + 1)
        params["encoder"] = {
            "layers": jax.vmap(lambda k: _init_encoder_layer(k, cfg))(ek[:-1]),
            "pos_emb": (jax.random.normal(
                ek[-1], (cfg.encoder.num_frames, cfg.d_model), jnp.float32
            ) * 0.02).astype(dtype),
            "final_norm": _zeros((cfg.d_model,), dtype),
        }
    return params


def is_global_flags(cfg: ModelConfig) -> Optional[np.ndarray]:
    """Per-layer bool array for local:global patterns, or None if uniform."""
    if cfg.attn is None:
        return None
    a = cfg.attn
    if a.global_layers:
        f = np.zeros(cfg.num_layers, bool)
        f[list(a.global_layers)] = True
        return f
    if a.global_every > 0:
        idx = np.arange(cfg.num_layers)
        return (idx % a.global_every) == (a.global_every - 1)
    return None


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed(params: dict, cfg: ModelConfig, inputs: jnp.ndarray) -> jnp.ndarray:
    if cfg.embeds_in and jnp.issubdtype(inputs.dtype, jnp.floating):
        return inputs.astype(jnp.dtype(cfg.dtype))
    # the gather's output follows the ids' (batch) sharding with the
    # feature dim whole; under an explicit-axes mesh JAX asks for it to be
    # stated, since the table itself is vocab-sharded
    ids = jax.typeof(inputs).sharding
    return params["embed"].at[inputs].get(
        out_sharding=ids.update(spec=P(*ids.spec, None)))


def unembed(params: dict, cfg: ModelConfig, h: jnp.ndarray) -> jnp.ndarray:
    """Logits over the *padded* vocab; pad rows masked to -inf (they carry
    zero probability under softmax/argmax/categorical)."""
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        w = params["embed"]
        logits = jnp.einsum("...d,vd->...v", h, w).astype(jnp.float32)
    else:
        logits = (h @ params["lm_head"]).astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab_size:
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask, -1e30, logits)
    return logits


# ---------------------------------------------------------------------------
# Whisper encoder
# ---------------------------------------------------------------------------


def encode(params: dict, cfg: ModelConfig, frames: jnp.ndarray) -> jnp.ndarray:
    """frames: (B, F, D) stub embeddings -> (B, F, D) encoder states."""
    enc = params["encoder"]
    h = frames.astype(jnp.dtype(cfg.dtype)) + enc["pos_emb"][None]
    a = cfg.attn
    B, F, _ = h.shape
    inp = AttnInputs(positions=jnp.broadcast_to(jnp.arange(F), (B, F)))

    def body(h, lp):
        u = rms_norm(h, lp["ln1"], cfg.norm_eps)
        out, *_ = attn_mod.prefill_attention(
            lp["attn"], a, u, inp, causal=False, rotary=False
        )
        h = h + out
        u = rms_norm(h, lp["ln2"], cfg.norm_eps)
        h = h + mlp_mod.apply(lp["mlp"], cfg, u)
        return h, None

    h, _ = jax.lax.scan(body, h, enc["layers"])
    return rms_norm(h, enc["final_norm"], cfg.norm_eps)


def encode_cross_kv(params: dict, cfg: ModelConfig, h_enc: jnp.ndarray):
    """Stacked (L, B, Se, KV, hd) cross K/V for every decoder layer."""
    a = cfg.attn
    cross = params["layers"]["cross"]
    return jax.vmap(lambda cp: attn_mod.encode_kv(cp, a, h_enc))(cross)


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


class PrefillResult(NamedTuple):
    logits: Optional[jnp.ndarray]  # (B, V) last-real-row logits, or (B, S, V)
    cache: Any  # decode cache pytree or None
    scores: Optional[jnp.ndarray]  # (L, B, H, n_score) f32
    aux: jnp.ndarray  # MoE load-balance loss (scalar f32)


def _policy_budget_schedule(cfg: ModelConfig, policy: str, budget: int,
                            beta: float):
    L = cfg.num_layers
    if policy == "pyramidkv":
        budgets = ev.pyramid_budgets(L, budget, beta)
        capacity = int(2.0 * beta / (beta + 1.0) * budget) + 1
    else:
        budgets = ev.uniform_budgets(L, budget)
        capacity = budget
    return budgets, capacity


def prefill(
    params: dict,
    cfg: ModelConfig,
    inputs: jnp.ndarray,  # (B, S) int tokens or (B, S, D) embeds
    *,
    lkv_params: Optional[dict] = None,  # lookahead rows + selective LoRA on
    policy: Optional[str] = None,  # eviction policy; None => no attn cache
    evict: Optional[EvictionConfig] = None,
    extra_slots: int = 0,  # empty tail capacity for decode appends
    capture_scores: bool = False,  # stack per-layer per-head scores (training)
    gt_boundary: Optional[int] = None,  # GT pass: X|Y boundary in ``inputs``
    mrope_positions: Optional[jnp.ndarray] = None,  # (3, B, S)
    encoder_embeds: Optional[jnp.ndarray] = None,  # whisper (B, F, D)
    want_logits: str = "last",  # "last" | "all" | "none"
    want_ssm_cache: bool = False,
    prompt_lens: Optional[jnp.ndarray] = None,  # (B,) true lens, <= n_real
    seeds: Optional[jnp.ndarray] = None,  # (B,) per-request seeds (random)
) -> PrefillResult:
    """``prompt_lens`` enables bucket-padded prefill (continuous-batching
    serving): inputs are right-padded to a shared bucket length, and every
    consumer of the padded rows is masked — they are invalid attention keys,
    carry zero eviction score, and never enter the decode cache.  Appended
    observation rows take positions after each request's *true* length, so
    the lookaheadkv scoring pass is exact under padding (its observation
    queries are learned rows at static offsets, unlike the sliding
    observation windows of the snapkv-family baselines, which become
    approximate for padded requests)."""
    a = cfg.attn
    lk = cfg.lookahead
    evict = evict or EvictionConfig()
    use_lookahead_rows = (policy == "lookaheadkv") or (
        capture_scores and lkv_params is not None and gt_boundary is None
    )
    if prompt_lens is not None:
        assert not cfg.uses_ssm and not cfg.is_encoder_decoder, \
            "bucket-padded prefill supports attention-only archs"
        assert gt_boundary is None, "prompt_lens and gt_boundary are exclusive"

    h = embed(params, cfg, inputs)
    B, n_real = h.shape[:2]
    lookahead_mask = None
    if use_lookahead_rows:
        assert lkv_params is not None, "lookaheadkv needs trained modules"
        h, lookahead_mask = append_lookahead(h, lkv_params)
    S = h.shape[1]
    col = jnp.arange(S)
    positions = jnp.broadcast_to(col, (B, S))
    key_valid = None  # (B, S) valid-key mask under bucket padding
    if prompt_lens is not None:
        pl = prompt_lens.astype(jnp.int32)
        # observation rows sit right after each request's true prompt, not
        # after the padding, so their rotary positions match unpadded prefill
        positions = jnp.where(col[None, :] < n_real, positions,
                              pl[:, None] + (col[None, :] - n_real))
        key_valid = (col[None, :] < pl[:, None]) | (col[None, :] >= n_real)
    mrope = None
    if a is not None and a.mrope:
        if mrope_positions is None:
            mrope = text_mrope_positions(positions)
        elif mrope_positions.shape[2] < S:  # extend for lookahead rows
            extra = S - mrope_positions.shape[2]
            mx = mrope_positions.max(axis=2, keepdims=True)
            ext = mx + 1 + jnp.broadcast_to(jnp.arange(extra), (3, B, extra))
            mrope = jnp.concatenate([mrope_positions, ext], axis=2)
        else:
            mrope = mrope_positions

    # --- score/eviction geometry (static) ---
    needs_scores = capture_scores or (policy in OBS_POLICIES)
    obs_policy = policy if policy in OBS_POLICIES else None
    if capture_scores and obs_policy is None:
        obs_policy = "gt_oracle" if gt_boundary is not None else "lookaheadkv"
    window_size = lk.window_size if lk else 32
    if obs_policy in ("lookaheadkv",):
        boundary = n_real  # obs rows appended after the real prompt
    elif obs_policy == "gt_oracle":
        assert gt_boundary is not None
        boundary = gt_boundary
    elif obs_policy in ("snapkv", "pyramidkv"):
        boundary = S - window_size
    elif obs_policy == "tova":
        boundary = S - 1
    elif obs_policy == "h2o":
        boundary = S
    else:
        boundary = S
    n_obs = S - boundary if obs_policy != "h2o" else S
    n_keys = boundary if obs_policy in ("lookaheadkv", "gt_oracle") else n_real
    do_evict = policy is not None and cfg.uses_attention
    adaptive_heads = (do_evict and evict.head_alloc == "adaptive"
                      and policy not in ("full",))
    if do_evict:
        budgets, _ = _policy_budget_schedule(
            cfg, policy, evict.budget if policy != "full" else n_keys,
            evict.pyramid_beta,
        )
        # the one source of truth for cache depth — the serving engines size
        # their live slot caches with the same function
        capacity = decode_cache_capacity(cfg, policy, evict, n_keys_max=n_keys)
    else:
        budgets = jnp.zeros((cfg.num_layers,), jnp.int32)
        capacity = 0

    # hybrid archs need their recurrent cache whenever a decode cache is built
    want_ssm_cache = want_ssm_cache or (do_evict and cfg.uses_ssm)

    flags = is_global_flags(cfg)
    patterned = flags is not None
    ls = lora_scale(cfg) if (lkv_params is not None and use_lookahead_rows) else 1.0
    lora_tree = (lkv_params or {}).get("lora") if use_lookahead_rows else None

    inp = AttnInputs(
        positions=positions, mrope_positions=mrope,
        lookahead_mask=lookahead_mask,
    )

    # whisper: run encoder once, stack cross K/V as scan xs
    cross_kv = None
    if cfg.is_encoder_decoder:
        assert encoder_embeds is not None, "whisper needs frame embeddings"
        h_enc = encode(params, cfg, encoder_embeds)
        cross_kv = encode_cross_kv(params, cfg, h_enc)

    xs: dict = {"p": params["layers"]}
    if lora_tree is not None:
        xs["lora"] = lora_tree
    if patterned:
        xs["flag"] = jnp.asarray(flags)
    if do_evict:
        xs["budget"] = budgets
    if cross_kv is not None:
        xs["ck"], xs["cv"] = cross_kv

    def body(h, x):
        lp = x["p"]
        lora_l = x.get("lora")
        flag = x.get("flag", True)
        ys: dict = {}
        q = k = v = None
        if cfg.uses_attention or cfg.uses_ssm:
            u = rms_norm(h, lp["ln1"], cfg.norm_eps)
            delta = 0.0
            if cfg.uses_attention:
                a_out, q, k, v = attn_mod.prefill_attention(
                    lp["attn"], a, u, inp, is_global=flag,
                    lora=None if lora_l is None else lora_l.get("attn"),
                    lora_scale=ls, kv_mask=key_valid,
                )
                delta = delta + a_out
            if cfg.uses_ssm:
                # Observation rows (lookahead tokens or a draft suffix) must
                # not pollute the cached recurrent state: run the real prompt
                # first, cache its state, then chain the observation segment.
                split = None
                if use_lookahead_rows:
                    split = n_real
                elif gt_boundary is not None:
                    split = gt_boundary
                if split is not None and split < S:
                    s_out1, ssm_cache = ssm_mod.apply(
                        lp["ssm"], cfg, u[:, :split]
                    )
                    s_out2, _ = ssm_mod.apply(
                        lp["ssm"], cfg, u[:, split:],
                        lora=(lora_l.get("ssm")
                              if (lora_l and use_lookahead_rows) else None),
                        lora_mask=jnp.ones((B, S - split, 1), u.dtype),
                        lora_scale=ls,
                        initial_state=ssm_cache["state"],
                        conv_tail=ssm_cache["conv"],
                    )
                    s_out = jnp.concatenate([s_out1, s_out2], axis=1)
                else:
                    s_out, ssm_cache = ssm_mod.apply(lp["ssm"], cfg, u)
                delta = delta + s_out
                if want_ssm_cache:
                    ys["ssm"] = ssm_cache
            if cfg.hybrid:
                delta = delta * 0.5
            h = h + delta
        if cfg.is_encoder_decoder:
            u_cross = rms_norm(h, lp["ln_cross"], cfg.norm_eps)
            h = h + attn_mod.cross_attention(
                lp["cross"], a, u_cross, x["ck"], x["cv"],
                lora=None if lora_l is None else lora_l.get("cross"),
                lora_mask=lookahead_mask, lora_scale=ls,
            )
            if do_evict and evict.cross_budget > 0 and n_obs > 0:
                # beyond-paper: evict the *encoder* KV with the same
                # observation queries (non-causal: all frames visible)
                B_, Se = u_cross.shape[0], x["ck"].shape[1]
                qc = attn_mod.linear(
                    u_cross[:, boundary:], lp["cross"]["wq"],
                    lp["cross"].get("bq"),
                ).reshape(B_, -1, a.num_heads, a.head_dim)
                sc = scoring.observation_scores(qc, x["ck"], Se, q_offset=Se)
                sc = scoring.postprocess(
                    sc, a.num_kv_heads, lk.pool_kernel if lk else 7)
                ys["cross_cache"] = dict(ev.evict_layer(
                    sc, x["ck"], x["cv"], min(evict.cross_budget, Se)
                )._asdict())
        h, ys["aux"], _ = _ffn_residual(h, lp, cfg, lora_l=lora_l,
                                        lora_mask=lookahead_mask, ls=ls)

        # ---- scoring + eviction (attention archs only) ----
        if cfg.uses_attention and needs_scores and obs_policy is not None:
            win = layer_window(a, flag)
            if obs_policy == "h2o":
                s_qh = scoring.observation_scores(
                    q, k, n_keys, window=win, q_offset=0,
                    kv_mask=None if key_valid is None else key_valid[:, :n_keys],
                )
            else:
                s_qh = scoring.observation_scores(
                    q[:, boundary:], k, boundary, window=win,
                    kv_mask=None if key_valid is None else key_valid[:, :boundary],
                )
            if capture_scores:
                ys["scores"] = s_qh
        if do_evict and cfg.uses_attention:
            prompt_valid = None if key_valid is None else key_valid[:, :n_keys]
            if policy in OBS_POLICIES:
                s_kv = scoring.postprocess(
                    s_qh, a.num_kv_heads, lk.pool_kernel if lk else 7
                )
                if policy in ("snapkv", "pyramidkv", "tova"):
                    # scored keys cover [0, boundary); force-keep the window
                    pad = n_keys - s_kv.shape[-1]
                    if pad > 0:
                        s_kv = jnp.pad(s_kv, ((0, 0), (0, 0), (0, pad)))
                    s_kv = ev.keep_window(s_kv, S - boundary)
            else:
                s_kv = ev.position_scores(
                    policy, n_keys, B, a.num_kv_heads, sink=evict.sink,
                    seeds=seeds,
                )
            if prompt_valid is not None:
                # padded keys rank last (max-pool may have bled real-neighbour
                # mass into them) and are masked out of the cache regardless
                s_kv = jnp.where(prompt_valid[:, None, :], s_kv, -1e30)
            hb = None
            if adaptive_heads:
                # -1e30 pad sentinels would corrupt the head-mass totals
                s_mass = s_kv if prompt_valid is None else jnp.maximum(s_kv, 0.0)
                hb = ev.adaptive_head_budgets(s_mass, evict.budget, capacity)
            cache_l = ev.evict_layer(
                s_kv, k[:, :n_keys], v[:, :n_keys], capacity,
                layer_budget=None if adaptive_heads else x.get("budget"),
                head_budgets=hb, extra_slots=extra_slots,
                key_mask=prompt_valid,
            )
            ys["cache"] = dict(cache_l._asdict())
        return h, ys

    h, ys = jax.lax.scan(body, h, xs)

    scores = ys.get("scores") if capture_scores else None
    aux = ys["aux"].sum()

    cache = None
    if do_evict or (want_ssm_cache and cfg.uses_ssm):
        cache = {}
        if "cache" in ys:
            cache["attn"] = ys["cache"]
            cache["cursor"] = jnp.asarray(capacity + 0, jnp.int32)
        if "ssm" in ys:
            cache["ssm"] = ys["ssm"]
        if cross_kv is not None:
            if "cross_cache" in ys:
                cache["cross"] = ys["cross_cache"]
            else:
                cache["cross"] = {"k": xs["ck"], "v": xs["cv"]}
        if prompt_lens is not None:
            cache["next_pos"] = pl[:, None]
        else:
            next_pos = gt_boundary if gt_boundary is not None else n_real
            cache["next_pos"] = jnp.full((B, 1), next_pos, jnp.int32)

    logits = None
    if want_logits == "last":
        # for GT/draft-scoring passes the "current" position is the X|Y
        # boundary, not the end of the appended observation rows
        if prompt_lens is not None:  # last *real* row per request
            logits = unembed(params, cfg, h[jnp.arange(B), pl - 1])
        else:
            row = (gt_boundary if gt_boundary is not None else n_real) - 1
            logits = unembed(params, cfg, h[:, row])
    elif want_logits == "all":
        logits = unembed(params, cfg, h[:, :n_real])
    return PrefillResult(logits=logits, cache=cache, scores=scores, aux=aux)


# ---------------------------------------------------------------------------
# Chunked prefill (streaming eviction scores)
# ---------------------------------------------------------------------------
#
# ``prefill`` above runs the whole prompt as one program — one compile per
# (prompt-bucket, batch) shape, and a long prompt monopolizes the device for
# its whole forward pass.  The chunked path streams fixed-size (B, chunk)
# token blocks instead:
#
#   * each chunk projects its K/V and appends them into a materialized
#     prompt buffer (``attention.chunk_prefill_attention`` — cross-chunk
#     flash attention over prior keys + causal self-attention, with a
#     *traced* chunk offset, so one compiled program serves every chunk of
#     every prompt length);
#   * a per-policy ``ScoreState`` (core/scoring.py) accumulates eviction
#     scores online — h2o sums per-key column masses chunk by chunk, taking
#     them directly from the attention kernel's *fused* second output
#     (``ops.chunk_attention(..., score_masses=True)``; no dense (C, K)
#     probability block on the hot path), the snapkv/pyramidkv/tova family
#     rolls the newest observation-window queries, and lookaheadkv/
#     gt_oracle defer to a final observation pass — both scored at prompt
#     end through the masked streaming ``ops.lookahead_score`` primitive;
#   * ``prefill_finalize`` runs the *same* ``evict_layer`` once at prompt
#     end, so the evicted cache matches monolithic prefill exactly (same
#     kept (layer, head, position) sets; logits bitwise on the reference
#     path, within fp tolerance otherwise).
#
# Chunked prefill serves attention(-plus-FFN/MoE) decoder-only archs — the
# same family the continuous-batching engine admits.


class ChunkState(NamedTuple):
    """Carried state of a streaming prefill: the materialized prompt KV and
    the policy's streaming score accumulator.  Buffer depth ``K`` bounds
    the prompt (plus observation rows) — it is HBM that limits prompt
    length, not a compile-time bucket table."""

    k: jnp.ndarray  # (L, B, K, KV, hd) prompt keys; col j = position j
    v: jnp.ndarray  # (L, B, K, KV, hd)
    score: scoring.ScoreState
    pos: jnp.ndarray  # () int32 — tokens streamed so far


def chunkable(cfg: ModelConfig) -> bool:
    a = cfg.attn
    return (cfg.uses_attention and not cfg.uses_ssm
            and not cfg.is_encoder_decoder and not a.mrope
            and not cfg.embeds_in)


def init_chunk_state(cfg: ModelConfig, policy: str, batch: int,
                     capacity: int) -> ChunkState:
    """Fresh streaming-prefill state with a ``capacity``-deep KV buffer.

    ``capacity`` must cover the prompt *plus* any appended observation rows
    (lookaheadkv's learned rows / gt_oracle's response suffix)."""
    assert chunkable(cfg), "chunked prefill serves attention-only archs"
    a = cfg.attn
    lk = cfg.lookahead
    dtype = jnp.dtype(cfg.dtype)
    L = cfg.num_layers
    kv = jnp.zeros((L, batch, capacity, a.num_kv_heads, a.head_dim), dtype)
    score = scoring.init_score_state(
        policy, L, batch, a.num_heads, a.head_dim, capacity,
        window_size=lk.window_size if lk else 32, dtype=dtype,
    )
    return ChunkState(k=kv, v=jnp.zeros_like(kv), score=score,
                      pos=jnp.zeros((), jnp.int32))


def snapshot_chunk_state(state: ChunkState, n: int) -> ChunkState:
    """Chunk-boundary snapshot of a streaming prefill: the first ``n``
    buffer columns of K/V plus the trimmed ``ScoreState`` — everything a
    later request sharing this ``n``-token prompt prefix needs to resume
    at ``pos = n``.

    Soundness (why the snapshot is shareable): every ``prefill_chunk``
    quantity at a boundary covered by *full* chunks is a pure function of
    the prefix tokens alone — attention is causal, the traced ``n_total``
    only gates rows at or past it (all prefix rows are valid whenever the
    requesting prompt is at least ``n`` long), and per-request seeds enter
    only at finalize.  So the snapshot taken while serving one request is
    bit-identical to the state any other request would have computed for
    the same prefix, at the same buffer capacity."""
    assert n <= state.k.shape[2], "snapshot deeper than the KV buffer"
    return ChunkState(
        k=state.k[:, :, :n], v=state.v[:, :, :n],
        score=state.score.snapshot(n), pos=jnp.asarray(n, jnp.int32),
    )


def resume_chunk_state(snap: ChunkState, capacity: int) -> ChunkState:
    """Inverse of ``snapshot_chunk_state``: zero-pad the trimmed buffers
    back to ``capacity`` (fresh buffers are zero-initialized, so the
    restored state is bitwise the state a request would have reached by
    streaming the prefix itself) and resume at ``pos = n``."""
    n = snap.k.shape[2]
    assert capacity >= n, f"capacity {capacity} < snapshot depth {n}"
    width = [(0, 0), (0, 0), (0, capacity - n), (0, 0), (0, 0)]
    return ChunkState(
        k=jnp.pad(snap.k, width), v=jnp.pad(snap.v, width),
        score=snap.score.restore(capacity), pos=jnp.asarray(n, jnp.int32),
    )


def _ffn_residual(h, lp, cfg: ModelConfig, *, lora_l=None, lora_mask=None,
                  ls: float = 1.0, smesh=None, count_rows=None):
    """The post-attention half of a block (MoE or MLP residual) — the one
    definition shared by monolithic prefill, the chunk step, the
    observation pass (which thread the lookahead LoRA), and decode.
    Returns (h, aux, routed) where aux is the MoE load-balance loss (zero
    otherwise) and routed the MoE layer's ``moe.routed_rows`` of the
    ``count_rows`` mask ((B, S) bool; None without one, or without MoE).
    With ``smesh`` (tensor-sharded serving) every FFN dot
    must keep the single-device summation order — GSPMD's realization is
    shape-dependent, so the dense MLP runs manual column-parallel TP
    (``mlp.apply_sharded``) and MoE runs replicated under shard_map
    (``attention.replicated_apply`` — exact but redundant; sharded-exact
    MoE dispatch is out of scope)."""
    aux = jnp.zeros((), jnp.float32)
    routed = None
    if cfg.moe is not None:
        u = rms_norm(h, lp["ln2"], cfg.norm_eps)
        moe_lora = None
        if lora_l is not None and lora_l.get("moe"):
            moe_lora = lora_l["moe"].get("shared")
        if cfg.moe.dispatch == "sparse":
            assert count_rows is None, "routed rows are counted densely"
            fn = lambda pp, uu, lo, lm: moe_mod.apply_sparse(
                pp, cfg, uu, lora=lo, lora_mask=lm, lora_scale=ls)
        else:
            fn = lambda pp, uu, lo, lm: moe_mod.apply(
                pp, cfg, uu, lora=lo, lora_mask=lm, lora_scale=ls,
                count_rows=count_rows)
        if smesh is not None:
            res = attn_mod.replicated_apply(
                fn, smesh, lp["moe"], u, moe_lora, lora_mask)
        else:
            res = fn(lp["moe"], u, moe_lora, lora_mask)
        mo, aux = res[:2]
        if count_rows is not None:
            routed = res[2]
        h = h + mo
    elif cfg.d_ff > 0:
        u = rms_norm(h, lp["ln2"], cfg.norm_eps)
        mlp_lora = None if lora_l is None else lora_l.get("mlp")
        mo = mlp_mod.apply_sharded(lp["mlp"], cfg, u, smesh, lora=mlp_lora,
                                   lora_mask=lora_mask, lora_scale=ls)
        h = h + mo
    return h, aux, routed


def prefill_chunk(
    params: dict,
    cfg: ModelConfig,
    state: ChunkState,
    tokens: jnp.ndarray,  # (B, chunk) int tokens; rows past n_total are pad
    n_total: jnp.ndarray,  # () int32 — true prompt length (shared across B)
    *,
    policy: str,
    mesh=None,  # serving mesh: per-shard head dispatch in the chunk kernel
    routed: Optional[jnp.ndarray] = None,  # (E_held,) int32 MoE tally
):
    """Process one fixed-size prompt chunk starting at ``state.pos``.

    Returns (state', logits (B, V) of the chunk's last *real* row) — the
    caller keeps the final chunk's logits as the prompt's next-token
    distribution.  Pad rows in a partial final chunk are harmless: causal
    masking hides their keys from every real row, they carry zero score
    weight, and the finalize step masks their buffer columns out of the
    cache.  With ``routed`` (an MoE model's running tally) a third output
    adds the chunk's real rows routed to each held expert, summed over the
    layers (``moe.routed_rows``).
    """
    a = cfg.attn
    assert chunkable(cfg), "chunked prefill serves attention-only archs"
    h = embed(params, cfg, tokens)
    B, C = h.shape[:2]
    s = state.pos
    positions = jnp.broadcast_to(s + jnp.arange(C), (B, C))
    inp = AttnInputs(positions=positions, mesh=mesh)
    smesh = attn_mod.model_shard_mesh(mesh, a)
    flags = is_global_flags(cfg)

    xs: dict = {"p": params["layers"], "k": state.k, "v": state.v}
    if flags is not None:
        xs["flag"] = jnp.asarray(flags)
    if state.score.acc is not None:
        xs["acc"] = state.score.acc
    if state.score.qbuf is not None:
        xs["qbuf"] = state.score.qbuf

    # cumulative policies take their per-chunk column-mass partials straight
    # from the attention kernel's fused second output — no dense score block
    want_masses = policy in scoring.STREAMING_CUMULATIVE
    count_rows = None
    if routed is not None:
        count_rows = jnp.broadcast_to(s + jnp.arange(C) < n_total, (B, C))

    def body(h, x):
        lp = x["p"]
        flag = x.get("flag", True)
        h = attn_mod.pin_activations(h, mesh)
        u = rms_norm(h, lp["ln1"], cfg.norm_eps)
        out, q, k_buf, v_buf, masses = attn_mod.chunk_prefill_attention(
            lp["attn"], a, u, inp, x["k"], x["v"], q_offset=s,
            is_global=flag, score_masses=want_masses, n_total=n_total,
        )
        h = h + out
        h, _, routed_l = _ffn_residual(h, lp, cfg, smesh=smesh,
                                       count_rows=count_rows)
        ys: dict = {"k": k_buf, "v": v_buf}
        if routed_l is not None:
            ys["routed"] = routed_l
        acc_l, qbuf_l = scoring.update_layer_scores(
            policy, x.get("acc"), x.get("qbuf"), q, masses_l=masses,
            q_offset=s, n_total=n_total,
        )
        if acc_l is not None:
            ys["acc"] = acc_l
        if qbuf_l is not None:
            ys["qbuf"] = qbuf_l
        return h, ys

    h, ys = jax.lax.scan(body, h, xs)

    score = state.score
    if score.acc is not None:
        score = score._replace(
            acc=ys["acc"],
            cnt=score.cnt + jnp.clip(n_total - s, 0, C).astype(jnp.float32),
        )
    if score.qbuf is not None:
        score = score._replace(qbuf=ys["qbuf"])
    row = jnp.clip(n_total - 1 - s, 0, C - 1)
    logits = unembed(params, cfg, h[jnp.arange(B), row])
    state = ChunkState(k=ys["k"], v=ys["v"], score=score, pos=s + C)
    if routed is not None:
        return state, logits, routed + ys["routed"].sum(0)
    return state, logits


def _chunk_observation_pass(
    params: dict,
    cfg: ModelConfig,
    state: ChunkState,
    n_total: jnp.ndarray,
    *,
    policy: str,
    lkv_params: Optional[dict],
    obs_tokens: Optional[jnp.ndarray],
    mesh=None,
):
    """Final-chunk observation forward for lookaheadkv / gt_oracle: run the
    observation rows (learned lookahead rows / the GT response suffix)
    through the stack against the materialized prompt KV, appending their
    keys after the prompt so each row's softmax includes the observation
    keys exactly as in monolithic prefill.  Returns (k_buf, v_buf,
    obs_masses (L, B, H, K))."""
    a = cfg.attn
    B = state.k.shape[1]
    if policy == "lookaheadkv":
        assert lkv_params is not None, "lookaheadkv needs trained modules"
        emb = lkv_params["emb"].astype(jnp.dtype(cfg.dtype))
        n_obs = emb.shape[0]
        h = jnp.broadcast_to(emb[None], (B, n_obs, emb.shape[1]))
        lora_tree = lkv_params.get("lora")
        ls = lora_scale(cfg)
        lmask = jnp.ones((B, n_obs, 1), h.dtype)
    else:  # gt_oracle: the response rows are the observation window
        assert obs_tokens is not None, "gt_oracle needs the response rows"
        h = embed(params, cfg, obs_tokens)
        n_obs = h.shape[1]
        lora_tree, ls, lmask = None, 1.0, None
    positions = jnp.broadcast_to(n_total + jnp.arange(n_obs), (B, n_obs))
    inp = AttnInputs(positions=positions, lookahead_mask=lmask, mesh=mesh)
    smesh = attn_mod.model_shard_mesh(mesh, a)
    flags = is_global_flags(cfg)

    xs: dict = {"p": params["layers"], "k": state.k, "v": state.v}
    if lora_tree is not None:
        xs["lora"] = lora_tree
    if flags is not None:
        xs["flag"] = jnp.asarray(flags)

    K = state.k.shape[2]

    def body(h, x):
        lp = x["p"]
        lora_l = x.get("lora")
        flag = x.get("flag", True)
        with jax.named_scope("lookahead_rows"):
            h = attn_mod.pin_activations(h, mesh)
            u = rms_norm(h, lp["ln1"], cfg.norm_eps)
            out, q, k_buf, v_buf, _ = attn_mod.chunk_prefill_attention(
                lp["attn"], a, u, inp, x["k"], x["v"], q_offset=n_total,
                is_global=flag,
                lora=None if lora_l is None else lora_l.get("attn"),
                lora_scale=ls,
            )
            h = h + out
            h, _, _ = _ffn_residual(h, lp, cfg, lora_l=lora_l,
                                    lora_mask=lmask, ls=ls, smesh=smesh)
        # the masked streaming primitive scores the observation rows over
        # the whole buffer (mean over the n_obs rows, traced row base)
        with jax.named_scope("lookahead_scores"):
            masses = attn_mod.by_kind(a, flag, lambda w: (
                attn_mod.sharded_lookahead_score(
                    q, k_buf, K, q_offset=n_total, window=w, smesh=smesh)))
        return h, {"k": k_buf, "v": v_buf, "obs": masses}

    _, ys = jax.lax.scan(body, h, xs)
    return ys["k"], ys["v"], ys["obs"]


def prefill_finalize(
    params: dict,
    cfg: ModelConfig,
    state: ChunkState,
    n_total: jnp.ndarray,  # () int32 true prompt length
    *,
    policy: str,
    evict: Optional[EvictionConfig] = None,
    lkv_params: Optional[dict] = None,
    obs_tokens: Optional[jnp.ndarray] = None,  # (B, n_obs) gt_oracle only
    extra_slots: int = 0,
    seeds: Optional[jnp.ndarray] = None,  # (B,) request seeds (random policy)
    mesh=None,  # serving mesh: per-shard observation / window scoring
) -> dict:
    """Close a streaming prefill: run the deferred observation pass (if the
    policy has one), turn the accumulated ``ScoreState`` into eviction
    scores, and run ``evict_layer`` once per layer over the materialized
    buffer — producing the same decode-cache pytree as monolithic
    ``prefill`` (same kept slots; shapes sized by the buffer depth, with
    surplus slots masked invalid)."""
    a = cfg.attn
    lk = cfg.lookahead
    evict = evict or EvictionConfig()
    L, B, K = state.k.shape[:3]
    kbuf, vbuf = state.k, state.v
    obs_masses = None
    if policy in scoring.FINAL_OBS:
        kbuf, vbuf, obs_masses = _chunk_observation_pass(
            params, cfg, state, n_total, policy=policy,
            lkv_params=lkv_params, obs_tokens=obs_tokens, mesh=mesh,
        )
    smesh = attn_mod.model_shard_mesh(mesh, a)
    budgets, _ = _policy_budget_schedule(
        cfg, policy, evict.budget if policy != "full" else K,
        evict.pyramid_beta,
    )
    capacity = decode_cache_capacity(cfg, policy, evict, n_keys_max=K)
    adaptive = evict.head_alloc == "adaptive" and policy not in ("full",)
    key_mask = jnp.broadcast_to(jnp.arange(K)[None] < n_total, (B, K))
    flags = is_global_flags(cfg)

    xs: dict = {"k": kbuf, "v": vbuf, "budget": budgets}
    if flags is not None:
        xs["flag"] = jnp.asarray(flags)
    if state.score.acc is not None:
        xs["acc"] = state.score.acc
    if state.score.qbuf is not None:
        xs["qbuf"] = state.score.qbuf
    if obs_masses is not None:
        xs["obs"] = obs_masses

    def body(carry, x):
        flag = x.get("flag", True)
        with jax.named_scope("lookahead_scores"):
            if policy in OBS_POLICIES:
                def score(w):
                    return scoring.finalize_layer_scores(
                        policy, x["k"], n_total,
                        acc_l=x.get("acc"), cnt=state.score.cnt,
                        qbuf_l=x.get("qbuf"), obs_masses_l=x.get("obs"),
                        num_kv_heads=a.num_kv_heads,
                        pool_kernel=lk.pool_kernel if lk else 7,
                        window_size=lk.window_size if lk else 32,
                        window=w, smesh=smesh)

                # only the rolled-window policies score here under a window
                s_kv = (attn_mod.by_kind(a, flag, score)
                        if policy in scoring.STREAMING_WINDOW
                        else score(layer_window(a, flag)))
            else:
                s_kv = ev.position_scores(
                    policy, K, B, a.num_kv_heads, sink=evict.sink,
                    seeds=seeds,
                )
                s_kv = jnp.where(key_mask[:, None, :], s_kv, -1e30)
        with jax.named_scope("topk_evict"):
            hb = None
            if adaptive:
                hb = ev.adaptive_head_budgets(
                    jnp.maximum(s_kv, 0.0), evict.budget, capacity)
            cache_l = ev.evict_layer(
                s_kv, x["k"], x["v"], capacity,
                layer_budget=None if adaptive else x.get("budget"),
                head_budgets=hb, extra_slots=extra_slots, key_mask=key_mask,
            )
        return carry, dict(cache_l._asdict())

    _, attn_cache = jax.lax.scan(body, 0, xs)
    return {
        "attn": attn_cache,
        "cursor": jnp.asarray(capacity, jnp.int32),
        "next_pos": jnp.broadcast_to(n_total, (B, 1)).astype(jnp.int32),
    }


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def init_decode_cache(
    cfg: ModelConfig, batch: int, capacity: int, *, fill_len: int = 0,
    hot_slots: int = 0, per_slot_cursor: bool = False,
) -> dict:
    """Fresh cache pytree (used directly and via jax.eval_shape for the
    dry-run ShapeDtypeStructs).  ``fill_len`` marks the first slots valid —
    decode-shape dry-runs model a cache already holding ``seq_len`` tokens.

    ``per_slot_cursor`` gives every batch row (serving slot) its own append
    cursor — the continuous-batching layout where slots admit and retire
    requests independently."""
    dtype = jnp.dtype(cfg.dtype)
    L = cfg.num_layers
    cache: dict = {}
    if cfg.uses_attention:
        a = cfg.attn
        KV, hd = a.num_kv_heads, a.head_dim
        valid = jnp.arange(capacity) < fill_len
        cache["attn"] = {
            "k": jnp.zeros((L, batch, capacity, KV, hd), dtype),
            "v": jnp.zeros((L, batch, capacity, KV, hd), dtype),
            "pos": jnp.broadcast_to(
                jnp.arange(capacity, dtype=jnp.int32)[None, None, :, None],
                (L, batch, capacity, KV),
            ),
            "mask": jnp.broadcast_to(
                valid[None, None, :, None], (L, batch, capacity, KV)
            ),
        }
        cache["cursor"] = (
            jnp.full((batch,), fill_len, jnp.int32) if per_slot_cursor
            else jnp.asarray(fill_len, jnp.int32)
        )
        if hot_slots:
            assert not per_slot_cursor, \
                "split-cache decode uses the shared hot-ring counter"
            # split-cache decode: frozen prompt cache + replicated hot ring
            cache["attn"]["hot_k"] = jnp.zeros((L, batch, hot_slots, KV, hd),
                                               dtype)
            cache["attn"]["hot_v"] = jnp.zeros((L, batch, hot_slots, KV, hd),
                                               dtype)
            cache["attn"]["hot_pos"] = jnp.zeros((L, batch, hot_slots, KV),
                                                 jnp.int32)
            cache["attn"]["hot_mask"] = jnp.zeros((L, batch, hot_slots, KV),
                                                  bool)
            cache["cursor"] = jnp.asarray(0, jnp.int32)  # hot-ring counter
    if cfg.uses_ssm:
        s, di, nh, conv_dim = ssm_mod.dims(cfg)
        cache["ssm"] = {
            "conv": jnp.zeros((L, batch, s.conv_width - 1, conv_dim), dtype),
            "state": jnp.zeros((L, batch, nh, s.head_dim, s.d_state), jnp.float32),
        }
    if cfg.is_encoder_decoder:
        a = cfg.attn
        cache["cross"] = {
            "k": jnp.zeros((L, batch, cfg.encoder.num_frames, a.num_kv_heads,
                            a.head_dim), dtype),
            "v": jnp.zeros((L, batch, cfg.encoder.num_frames, a.num_kv_heads,
                            a.head_dim), dtype),
        }
    cache["next_pos"] = jnp.full((batch, 1), fill_len, jnp.int32)
    return cache


def add_decode_eviction_scores(cache: dict) -> dict:
    """Arm a decode cache for decoding-stage eviction (beyond-paper; see
    attention.decode_attention_step_evicting): valid prefill slots start
    with unit cumulative score — they already won prefill eviction."""
    attn = dict(cache["attn"])
    attn["score"] = cache["attn"]["mask"].astype(jnp.float32)
    out = dict(cache)
    out["attn"] = attn
    return out


# ---------------------------------------------------------------------------
# Slot-cache surgery (continuous-batching serving)
# ---------------------------------------------------------------------------
#
# Post-eviction decode caches are shape-uniform across prompt lengths — every
# request's cache is (budget + margin) slots regardless of n_in.  That is the
# property the continuous-batching engine exploits: a freshly prefilled
# request's cache pytree can be scattered into any free slot of the live
# slot-batched cache, mid-stream, without reshaping anything.

_SLOT_AXIS_0 = ("next_pos", "cursor")  # every other top-level group is (L, B, …)


def _slot_axis(path) -> int:
    top = None
    for p in path:
        if hasattr(p, "key"):
            top = str(p.key)
            break
    return 0 if top in _SLOT_AXIS_0 else 1


def decode_cache_capacity(cfg: ModelConfig, policy: str,
                          evict: EvictionConfig, *, n_keys_max: int) -> int:
    """Static kept-slot capacity of the decode cache that a prefill under
    ``policy`` produces for prompts up to ``n_keys_max`` tokens — the
    shape-uniformity contract the slot scheduler relies on."""
    _, capacity = _policy_budget_schedule(
        cfg, policy, evict.budget if policy != "full" else n_keys_max,
        evict.pyramid_beta,
    )
    if evict.head_alloc == "adaptive" and policy not in ("full",):
        capacity = int(evict.budget * evict.adaptive_ceiling)
    return min(capacity, n_keys_max)


def pad_cache_capacity(cache: dict, capacity: int) -> dict:
    """Right-pad the attention slot axis to ``capacity`` (mask=False): small
    buckets clamp the kept capacity below the budget, so their caches are
    shallower — padding restores the uniform live-cache shape."""
    attn = cache.get("attn")
    if attn is None:
        return cache
    C = attn["k"].shape[2]
    if C == capacity:
        return cache
    assert C < capacity, f"cache deeper ({C}) than live capacity ({capacity})"
    padded = {}
    for name, leaf in attn.items():
        if name.startswith("hot_"):
            padded[name] = leaf
            continue
        width = [(0, 0)] * leaf.ndim
        width[2] = (0, capacity - C)
        padded[name] = jnp.pad(leaf, width)
    out = dict(cache)
    out["attn"] = padded
    return out


def insert_request_cache(live: dict, req: dict, slot) -> dict:
    """Scatter a batch-1 request cache (from a bucketed prefill) into slot
    ``slot`` of the live slot-batched cache.  The request cache is
    capacity-padded first; its scalar cursor lands in the live per-slot
    cursor vector.  ``slot`` may be traced (the insert jits cleanly)."""
    if "attn" in live:
        req = pad_cache_capacity(req, live["attn"]["k"].shape[2])

    def ins(path, lv, rv):
        return jax.lax.dynamic_update_slice_in_dim(
            lv, rv.astype(lv.dtype), slot, axis=_slot_axis(path))

    out = jax.tree_util.tree_map_with_path(
        ins,
        {k: v for k, v in live.items() if k != "cursor"},
        {k: v for k, v in req.items() if k != "cursor"},
    )
    if "cursor" in live:
        out["cursor"] = jax.lax.dynamic_update_slice(
            live["cursor"],
            jnp.reshape(req["cursor"], (1,)).astype(live["cursor"].dtype),
            (slot,),
        )
    return out


def extract_request_cache(live: dict, slot) -> dict:
    """Slice slot ``slot`` back out as a batch-1 request cache — the inverse
    of ``insert_request_cache`` up to capacity padding."""

    def ext(path, lv):
        return jax.lax.dynamic_slice_in_dim(lv, slot, 1,
                                            axis=_slot_axis(path))

    out = jax.tree_util.tree_map_with_path(
        ext, {k: v for k, v in live.items() if k != "cursor"})
    if "cursor" in live:
        cur = live["cursor"]
        out["cursor"] = (jax.lax.dynamic_slice(cur, (slot,), (1,))
                         if cur.ndim else cur)
    return out


def select_cache_slots(active: jnp.ndarray, new_cache: dict,
                       old_cache: dict) -> dict:
    """Per-slot select between two structurally identical decode caches:
    slot b advances to ``new_cache`` where ``active[b]``, else keeps
    ``old_cache`` — retired / empty slots don't advance even though decode
    computes over the full slot batch."""

    def sel(path, new_leaf, old_leaf):
        if old_leaf.ndim == 0:  # legacy shared scalar cursor
            return new_leaf
        shape = [1] * new_leaf.ndim
        shape[_slot_axis(path)] = active.shape[0]
        return jnp.where(active.reshape(shape), new_leaf, old_leaf)

    return jax.tree_util.tree_map_with_path(sel, new_cache, old_cache)


def decode_step(
    params: dict,
    cfg: ModelConfig,
    token: jnp.ndarray,  # (B, 1) int tokens or (B, 1, D) embeds
    cache: dict,
    *,
    mrope_positions: Optional[jnp.ndarray] = None,  # (3, B, 1)
    mesh=None,  # enables shard_map'd frozen-cache attention (split cache)
    active: Optional[jnp.ndarray] = None,  # (B,) live slots (paged cache)
    paged_depth: Optional[int] = None,  # static dense-equivalent depth
) -> tuple[jnp.ndarray, dict]:
    """One decode step.  Returns (logits (B, V) f32, updated cache).

    A *paged* cache (``"pool"`` key — see serving/kv_pool.py) carries the
    shared per-layer block-pool arrays plus a per-slot block table under
    ``cache["attn"]["table"]``.  Because the pool is shared across slots,
    retired slots cannot be rolled back with ``select_cache_slots`` the
    way dense serving does — ``active`` gates the append scatter and the
    cursor / position advance in-step instead.  ``paged_depth`` is the
    static logical cache depth (the dense engine's capacity + margin):
    the Pallas kernel path attends in pool layout (dead rows beyond it
    are masked), while the jnp gather fallback slices its view to it so
    that path stays shape- and bit-identical to dense serving.

    Decode-time eviction scoring needs no plumbing here: when the serving
    engine threads a ``"score"`` leaf ((L, B, depth, KV) cumulative
    masses) inside ``cache["pool"]``, the layer scan slices it per layer
    like any other pool leaf and ``decode_attention_step_paged`` returns
    the accumulated copy in its cache dict, so the updated buffer rides
    ``ys`` back out with zero signature changes.
    """
    a = cfg.attn
    paged = "pool" in cache
    h = embed(params, cfg, token)
    B = h.shape[0]
    positions = cache["next_pos"]  # (B, 1)
    mrope = None
    if a is not None and a.mrope:
        mrope = (mrope_positions if mrope_positions is not None
                 else text_mrope_positions(positions))
    cursor = cache.get("cursor")
    flags = is_global_flags(cfg)
    patterned = flags is not None

    xs: dict = {"p": params["layers"]}
    if patterned:
        xs["flag"] = jnp.asarray(flags)
    if paged:
        assert paged_depth is not None, "paged decode needs its static depth"
        xs["attn_cache"] = cache["pool"]  # per-layer pool slices (L leading)
    elif cfg.uses_attention and "attn" in cache:
        xs["attn_cache"] = cache["attn"]
    if cfg.uses_ssm:
        xs["ssm_cache"] = cache["ssm"]
    cross_evicted = (cfg.is_encoder_decoder
                     and "mask" in cache.get("cross", {}))
    if cfg.is_encoder_decoder:
        if cross_evicted:
            xs["cross_cache"] = cache["cross"]
        else:
            xs["ck"] = cache["cross"]["k"]
            xs["cv"] = cache["cross"]["v"]

    inp_base = AttnInputs(
        positions=positions, mrope_positions=mrope,
        cache_cursor=cursor, mesh=mesh,
    )
    smesh = None if a is None else attn_mod.model_shard_mesh(mesh, a)

    def body(h, x):
        lp = x["p"]
        flag = x.get("flag", True)
        ys: dict = {}
        if cfg.uses_attention or cfg.uses_ssm:
            h = attn_mod.pin_activations(h, mesh)
            u = rms_norm(h, lp["ln1"], cfg.norm_eps)
            delta = 0.0
            if cfg.uses_attention and "attn_cache" in x:
                inp = inp_base._replace(cache=x["attn_cache"])
                win = layer_window(a, flag)
                if paged:
                    a_out, new_c = attn_mod.decode_attention_step_paged(
                        lp["attn"], a, u, inp, window=win, is_global=flag,
                        table=cache["attn"]["table"], depth=paged_depth,
                        active=active)
                else:
                    if "hot_k" in x["attn_cache"]:
                        step_fn = attn_mod.decode_attention_step_split
                    elif "score" in x["attn_cache"]:
                        step_fn = attn_mod.decode_attention_step_evicting
                    else:
                        step_fn = attn_mod.decode_attention_step
                    a_out, new_c = step_fn(lp["attn"], a, u, inp, window=win,
                                           is_global=flag)
                delta = delta + a_out
                ys["attn_cache"] = new_c
            if cfg.uses_ssm:
                s_out, new_s = ssm_mod.step(lp["ssm"], cfg, u, x["ssm_cache"])
                delta = delta + s_out
                ys["ssm_cache"] = new_s
            if cfg.hybrid:
                delta = delta * 0.5
            h = h + delta
        if cfg.is_encoder_decoder:
            u = rms_norm(h, lp["ln_cross"], cfg.norm_eps)
            if cross_evicted:
                h = h + attn_mod.cross_attention_decode_evicted(
                    lp["cross"], a, u, x["cross_cache"])
            else:
                h = h + attn_mod.cross_attention(lp["cross"], a, u,
                                                 x["ck"], x["cv"])
        h, _, _ = _ffn_residual(h, lp, cfg, smesh=smesh)
        return h, ys

    h, ys = jax.lax.scan(body, h, xs)
    logits = unembed(params, cfg, h[:, 0])

    new_cache = dict(cache)
    if paged:
        # pool writes were already active-gated in-step (null-routed);
        # the per-slot cursor / position advance is gated here for the
        # same reason — no post-hoc select over the shared pool exists
        new_cache["pool"] = ys["attn_cache"]
        adv_c = jnp.minimum(cursor + 1, paged_depth)
        adv_p = positions + 1
        if active is not None:
            adv_c = jnp.where(active, adv_c, cursor)
            adv_p = jnp.where(active[:, None], adv_p, positions)
        new_cache["cursor"] = adv_c
        new_cache["next_pos"] = adv_p
        return logits, new_cache
    if "attn_cache" in ys:
        new_cache["attn"] = ys["attn_cache"]
        if "hot_k" in cache["attn"]:
            new_cache["cursor"] = cursor + 1  # hot-ring counter
        else:
            cap = cache["attn"]["k"].shape[2]
            new_cache["cursor"] = jnp.minimum(cursor + 1, cap)
    if "ssm_cache" in ys:
        new_cache["ssm"] = ys["ssm_cache"]
    new_cache["next_pos"] = positions + 1
    return logits, new_cache
