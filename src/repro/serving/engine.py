"""Serving engines: chunked continuous batching (current), plus the
deprecated lockstep and bucketed engines kept as benchmark baselines.

``ContinuousEngine`` streams prefill in fixed-size chunks and interleaves
them with decode (vLLM-style mixed steps):

    arrivals ──> FCFS queue ──> chunked prefill ──> decode slots
                                 (one compiled       (one slot-batched
                                  (1, chunk) step,    chunked loop)
                                  streaming scores)
                        ▲                    │
                        └── token-budget step: every iteration runs one
                            decode chunk for the live slots *and* up to
                            budget/chunk prefill chunks of the in-flight
                            prompt — decode never stalls behind a prompt,
                            and prompt length is bounded by HBM (the KV
                            buffer grows geometrically), not by a bucket
                            table.

Admission still exploits the paper's eviction: every request's
post-eviction decode cache has the same static shape
``(budget_capacity + margin)`` regardless of prompt length, so a freshly
prefilled request's cache pytree is scattered into any free slot of the
live decode cache (``transformer.insert_request_cache``) without
reshaping.

Deprecated (importable, warn on construction):

* ``ServingEngine`` — the paper-shaped lockstep engine (one same-length
  batch, prefill and decode back-to-back).
* ``BucketedEngine`` — the previous continuous engine: pad-to-bucket
  prefill with a compile cache keyed ``(bucket, batch, policy, padded)``.
  A long prompt monopolizes the device for its whole (monolithic) prefill
  and prompts beyond the largest bucket force fresh compiles; kept so
  ``benchmarks/bench_serving.py`` can quantify exactly that.
"""

from __future__ import annotations

import time
import warnings
from collections.abc import Mapping
from contextlib import nullcontext
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import EvictionConfig, ModelConfig
from repro.core import policies
from repro.core.eviction import select_topk
from repro.kernels import ops
from repro.kernels.ref import NEG_INF
from repro.models import transformer as tf
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import request_track
from repro.serving.batching import (DEFAULT_BUCKETS, ChunkCompileCache,
                                    PrefillCompileCache, _batch_bucket,
                                    _bucket_for, _pad_to_bucket)
from repro.serving.config import (ChunkingConfig, DecodeEvictionConfig,
                                  ServingConfig)
from repro.serving.kv_pool import KVBlockPool, from_blocks, to_blocks
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import (Request, RequestState, SlotScheduler,
                                     plan_step)

__all__ = ["Request", "RequestState", "ServingEngine", "ContinuousEngine",
           "BucketedEngine", "ServingConfig", "DecodeEvictionConfig",
           "ChunkingConfig", "cache_bytes", "paged_sweep"]

# what a host phase enters when no recorder is attached: no span, no
# profiler annotation
_NO_SPAN = nullcontext()
# engine seconds between the trace's ``clock`` spans
CLOCK_EVERY_S = 1.0


def cache_bytes(cfg: ModelConfig, capacity: int, n_in: int) -> dict:
    """Analytic cache footprint: full vs evicted (the paper's headline)."""
    if cfg.attn is None:
        return {"full": 0, "evicted": 0, "ratio": 1.0}
    per_tok = cfg.num_layers * cfg.attn.kv_dim * 2 * 2  # K+V, bf16
    return {
        "full": n_in * per_tok,
        "evicted": capacity * per_tok,
        "ratio": n_in / max(capacity, 1),
    }


def _request_seeds(requests) -> jnp.ndarray:
    return jnp.asarray([r.eviction_seed for r in requests], jnp.int32)


def _snapshot(arr: np.ndarray) -> jnp.ndarray:
    """Freeze a host mirror for async dispatch.

    jax stages host→device transfers lazily, so an argument buffer the
    engine mutates in place after the call (cursor / position advance,
    retirement bookkeeping) can be read by the device *mid-flight* — the
    PR 5 bimodal-tokens race.  Hand jax a private copy, and mark that
    copy read-only so the next mirror added to the engine cannot silently
    reintroduce the race by reusing a handed-off buffer as its mirror:
    any in-place write to it raises instead of corrupting a dispatch.
    """
    c = np.array(arr)  # always a fresh contiguous buffer, never a view
    c.flags.writeable = False
    return jnp.asarray(c)


@partial(jax.jit,
         static_argnames=("capacity", "depth", "block_size", "nb_keep"))
def paged_sweep(pool: dict, score: jnp.ndarray, table: jnp.ndarray,
                slot: jnp.ndarray, *, capacity: int, depth: int,
                block_size: int, nb_keep: int) -> tuple[dict, jnp.ndarray]:
    """Evict-and-compact one slot's paged decode cache in place.

    The device half of a decode-eviction sweep: gather the slot's dense
    ``[0, depth)`` view through its block table, keep the ``capacity``
    highest cumulative-attention rows per (layer, kv head) — the same
    H2O heavy-hitter rule the dense ``decode_attention_step_evicting``
    applies per step, batched over the whole window — compact them into
    the first ``nb_keep`` blocks of the run (temporal order preserved,
    exactly like prefill eviction), and zero everything past them.  The
    host then frees the tail blocks ``[nb_keep, nb)`` back to the pool
    and resets the slot's cursor to ``capacity``.

    Every block covering ``[0, depth)`` must be real (non-null) when
    this runs — the host fills table gaps first — because the compacted
    rows are scattered back through those same table entries.

    ``score`` is the engine's ``(L, num_slots, depth, KV)`` cumulative
    mass buffer; kept rows carry their tallies across sweeps (H2O
    semantics), evicted and padded rows restart at zero.
    """
    bs = block_size
    nb = -(-depth // bs)  # blocks covering logical rows [0, depth)
    row = table[slot, :nb]  # (nb,) physical block ids

    def dense(leaf):  # (L, NB, KV, bs, ...) -> (L, depth, KV, ...)
        return from_blocks(leaf[:, row])[:, :depth]

    k = dense(pool["k"])  # (L, depth, KV, hd)
    v = dense(pool["v"])
    pos = dense(pool["pos"])  # (L, depth, KV)
    mask = dense(pool["mask"])
    sc = score[:, slot]  # (L, depth, KV) cumulative masses
    # top-capacity rows per (layer, kv head); invalid rows can never win
    # except on overflow, where their gathered mask stays False
    sel = jnp.moveaxis(jnp.where(mask, sc, NEG_INF), 1, 2)  # (L, KV, depth)
    idx, selmask = select_topk(sel, capacity)  # (L, KV, cap), temporal order

    def take(x):  # (L, depth, KV[, hd]) -> (L, cap, KV[, hd])
        xt = jnp.moveaxis(x, 1, 2)  # (L, KV, depth, ...)
        ix = idx if xt.ndim == 3 else idx[..., None]
        g = jnp.take_along_axis(xt, ix.astype(jnp.int32), axis=2)
        return jnp.moveaxis(g, 2, 1)

    kept = take(mask) & jnp.moveaxis(selmask, 1, 2)  # (L, cap, KV)
    k = jnp.where(kept[..., None], take(k), 0)
    v = jnp.where(kept[..., None], take(v), 0)
    pos = jnp.where(kept, take(pos), 0)
    sc_keep = jnp.where(kept, take(sc), 0.0)

    def pad(x, rows):  # (L, cap, ...) -> (L, rows, ...)
        cfgpad = [(0, 0)] * x.ndim
        cfgpad[1] = (0, rows - x.shape[1])
        return jnp.pad(x, cfgpad)

    def blk(x):  # (L, cap, KV, ...) -> (L, nb_keep, KV, bs, ...)
        return to_blocks(x, nb_keep, bs)

    keep_ids = row[:nb_keep]
    newpool = {
        "k": pool["k"].at[:, keep_ids].set(blk(k)),
        "v": pool["v"].at[:, keep_ids].set(blk(v)),
        "pos": pool["pos"].at[:, keep_ids].set(blk(pos)),
        "mask": pool["mask"].at[:, keep_ids].set(blk(kept)),
    }
    score = score.at[:, slot].set(pad(sc_keep, depth))
    return newpool, score


class ServingEngine:
    """Deprecated lockstep batch engine: every request in a batch shares one
    prompt length, and prefill/decode run back-to-back for the whole batch.
    Kept as the paper-shaped baseline for benchmarks and exactness tests."""

    def __init__(
        self,
        params: dict,
        cfg: ModelConfig,
        *,
        policy: str = "lookaheadkv",
        evict: Optional[EvictionConfig] = None,
        lkv_params: Optional[dict] = None,
        draft_params: Optional[dict] = None,
        draft_cfg: Optional[ModelConfig] = None,
        max_new_tokens: int = 64,
        eos_id: int = 0,
        decode_evict: bool = False,
    ):
        warnings.warn(
            "ServingEngine (lockstep) is deprecated; serve through the "
            "chunked ContinuousEngine", DeprecationWarning, stacklevel=2)
        self.params, self.cfg = params, cfg
        self.policy = policy
        self.evict = evict if evict is not None else EvictionConfig()
        self.lkv_params = lkv_params
        self.draft_params, self.draft_cfg = draft_params, draft_cfg
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        # decoding-stage eviction (beyond-paper): the cache stays at
        # ``budget + margin`` even for long generations — new tokens evict
        # the lowest cumulative-attention slots once capacity is reached.
        # The dense engines only consume the margin rule; the paged
        # ContinuousEngine also reads ``interval`` (sweep period).
        self.decode_evict = DecodeEvictionConfig.coerce(decode_evict)
        self.decode_margin = self.decode_evict.margin_rows(max_new_tokens)
        self._prefill_fn = jax.jit(self._prefill)
        self._decode_fn = jax.jit(self._decode)

    # -- jit bodies ---------------------------------------------------------
    def _prefill(self, params, lkv, tokens, seeds):
        res = policies.run_eviction(
            self.policy, params, self.cfg, tokens, evict=self.evict,
            lkv_params=lkv, draft_params=self.draft_params,
            draft_cfg=self.draft_cfg, extra_slots=self.decode_margin,
            seeds=seeds,
        )
        if self.decode_evict.enabled:
            res = res._replace(cache=tf.add_decode_eviction_scores(res.cache))
        return res

    def _decode(self, params, first_token, cache):
        return policies.greedy_decode(
            params, self.cfg, first_token, cache, self.max_new_tokens
        )

    # -- public API ----------------------------------------------------------
    def serve(self, requests: list[Request]) -> list[Request]:
        """Serve a batch of same-length requests.

        ``ttft_s`` here is *batch-level by construction* — all requests
        prefill together, so they share one first-token time.  Per-request
        TTFT under mixed traffic is what ``ContinuousEngine`` reports.
        """
        assert requests, "empty batch"
        n_in = len(requests[0].prompt)
        assert all(len(r.prompt) == n_in for r in requests), \
            "batch requests by prompt length"
        tokens = jnp.asarray(np.stack([r.prompt for r in requests]))
        t0 = time.perf_counter()
        res = self._prefill_fn(self.params, self.lkv_params, tokens,
                               _request_seeds(requests))
        res.logits.block_until_ready()
        ttft = time.perf_counter() - t0
        first = jnp.argmax(res.logits, -1)[:, None].astype(jnp.int32)
        toks, _ = self._decode_fn(self.params, first, res.cache)
        toks = np.asarray(toks)  # (B, max_new_tokens)
        for i, r in enumerate(requests):
            seq = toks[i].tolist()
            if self.eos_id in seq:
                seq = seq[: seq.index(self.eos_id) + 1]
            r.out_tokens = seq
            r.ttft_s = ttft
            r.first_token_s = ttft
            r.done = True
            r.state = RequestState.DONE
        return requests

    def cache_bytes(self, n_in: int) -> dict:
        cap = self.evict.budget + self.decode_margin
        return cache_bytes(self.cfg, cap, n_in)

    def kv_device_bytes(self, batch: int = 1) -> int:
        """K+V bytes of one served batch's decode cache (the lockstep
        engine holds no persistent slot cache between batches)."""
        a = self.cfg.attn
        if a is None:
            return 0
        per_row = 2 * self.cfg.num_layers * a.kv_dim \
            * jnp.dtype(self.cfg.dtype).itemsize
        return batch * (self.evict.budget + self.decode_margin) * per_row


class _InflightPrefill:
    """Host-side cursor of the one streaming prefill in flight.  ``tip``
    is the deepest pinned prefix-cache entry along this request's prompt
    (the resume point on a hit, then each freshly inserted boundary)."""

    __slots__ = ("req", "state", "n", "s", "logits", "tip", "routed")

    def __init__(self, req: Request, state, n: int):
        self.req, self.state, self.n = req, state, n
        self.s = 0
        self.logits = None
        self.tip = None
        self.routed = None  # MoE: device tally of this request's chunks


class _SlotDecodeMixin:
    """The slot-batched decode loop shared by both continuous engines:
    jitted chunks of 1/2/4/… steps with per-slot cursors and an active
    mask.  Expects ``self.params/cfg/eos_id/_chunks`` and a
    ``self._decode_fns`` dict."""

    #: decode chunk lengths we are willing to compile
    _CHUNK_SIZES = (1, 2, 4, 8, 16)

    def _phase(self, name: str, tid: Optional[str] = None, **args):
        """A host phase: a recorder span (and profiler annotation) on
        ``tid``, the engine track by default, when a recorder is
        attached; else a shared no-op context."""
        tr = getattr(self, "trace", None)
        if tr is None:
            return _NO_SPAN
        return tr.span(name, tid or tr.ENGINE, **args)

    def _decode_fn(self, steps: int):
        fn = self._decode_fns.get(steps)
        if fn is None:
            sampling = getattr(self, "sampling", None)
            mesh = getattr(self, "mesh", None)

            def body(params, tok, cache, active, seeds):
                return policies.decode_chunk(
                    params, self.cfg, tok, cache, steps, active=active,
                    sampling=sampling, seeds=seeds, mesh=mesh)

            fn = jax.jit(body)
            self._decode_fns[steps] = fn
        return fn

    def _pick_chunk(self, remaining, active) -> int:
        """Largest configured chunk no bigger than the *longest* remaining
        stream: slots that finish mid-chunk simply have their surplus tokens
        truncated at collect time (greedy decode makes outputs prefix-stable,
        so overshoot wastes a few slot-steps but never changes tokens), which
        keeps the host-dispatch count low near retirements."""
        if not active.any():
            return 1
        room = max(int(remaining[active].max()), 1)
        steps = 1
        for c in self._chunks:
            if c <= room:
                steps = c
        return steps

    def _collect(self, toks, steps, sched, active, remaining, last_emit, t0):
        now = time.perf_counter() - t0
        for slot in np.nonzero(active)[0]:
            r = sched.running[slot]
            r.max_gap_s = max(r.max_gap_s, now - last_emit[slot])
            last_emit[slot] = now
            take = min(steps, int(remaining[slot]))  # drop overshoot tokens
            finished = False
            for t in toks[slot, :take].tolist():
                r.out_tokens.append(int(t))
                if int(t) == self.eos_id:
                    finished = True
                    break
            remaining[slot] -= steps
            if finished or remaining[slot] <= 0:
                sched.retire(r, now=now)
                active[slot] = False
                self._on_retire(slot, r)
                m = getattr(self, "_m_retired", None)
                if m is not None:
                    m.inc()
                tr = getattr(self, "trace", None)
                tid = request_track(r.uid)
                if tr is not None:
                    tr.end("decode", tid)
                # gt_oracle harvest: the retired request carries the very
                # future the oracle policy needs (its generated tokens), so
                # this is the one moment importance targets can be captured
                # from live traffic (deprecated engines lack the hook)
                h = getattr(self, "harvest", None)
                if h is not None:
                    with self._phase("harvest", tid):
                        h.on_retire(r)
                # lookahead drift monitor (repro.obs.quality): same moment,
                # same reason — the generated future is in hand
                d = getattr(self, "drift", None)
                if d is not None:
                    d.on_retire(r)
                if tr is not None:
                    tr.instant("retire", tid, tokens=len(r.out_tokens))
                    tr.end("request", tid, outcome="done")
                self._release_slot(slot)

    def _on_retire(self, slot: int, req: Request) -> None:
        """Retirement hook, called while the slot's cache still exists:
        the paged engine captures the request's final kept set here when
        ``capture_admission`` asks for it (the paged counterpart of the
        dense engines' inspectable slot cache)."""

    def _release_slot(self, slot: int) -> None:
        """Retirement hook: the paged engine returns the slot's KV blocks
        to the pool here — the memory half of retiring (dense slot caches
        have nothing to free)."""


class _LegacyStatsView(Mapping):
    """Read-only mapping reproducing the pre-registry ``engine.stats``
    dict — same keys, same conditional presence — from the typed metrics
    registry, so external readers keep working through the deprecation.
    Empty before the first ``run()``; the nested component dicts
    (``prefix_cache`` / ``prefix`` / ``kv_pool``) are computed live from
    the components instead of being frozen at run end."""

    __slots__ = ("_eng",)

    def __init__(self, eng: "ContinuousEngine"):
        self._eng = eng

    def _as_dict(self) -> dict:
        e = self._eng
        if not e._run_started:
            return {}
        v = e.metrics.value
        d = {
            "prefill_chunks": int(v("serving_prefill_chunks_total")),
            "decode_chunks": int(v("serving_decode_chunks_total")),
            "decode_steps": int(v("serving_decode_steps_total")),
            "decode_time_s": float(v("serving_decode_seconds_total")),
            "max_prefill_between_decode":
                int(v("serving_max_prefill_between_decode")),
            "max_concurrency": int(v("serving_max_concurrency")),
        }
        d.update(e._run_info)
        if e.prefix_cache is not None:
            d["prefix_hits"] = int(v("serving_prefix_hits_total"))
            d["prefix_misses"] = int(v("serving_prefix_misses_total"))
            d["prefix_tokens_skipped"] = \
                int(v("serving_prefix_tokens_skipped_total"))
            d["prefix_cache"] = e.prefix_cache.stats()
            if e._last_sched is not None:
                d["prefix"] = e._last_sched.prefix_stats()
        if e.pool is not None:
            d["preemptions"] = int(v("serving_preemptions_total"))
            d["admission_blocked"] = \
                int(v("serving_admission_blocked_total"))
            if e._score_dev is not None:
                d["decode_evict_sweeps"] = \
                    int(v("serving_decode_evict_sweeps_total"))
            if e._last_sched is not None:
                d["kv_pool"] = e._last_sched.pool_stats()
        if e.cfg.moe is not None:
            d["moe.routed_rows"] = e.routed_rows()
        return d

    def __getitem__(self, key):
        return self._as_dict()[key]

    def __iter__(self):
        return iter(self._as_dict())

    def __len__(self):
        return len(self._as_dict())

    def __repr__(self):
        return f"_LegacyStatsView({self._as_dict()!r})"


class ContinuousEngine(_SlotDecodeMixin):
    """Chunked continuous-batching engine: streaming prefill interleaved
    with a slot-batched decode loop under a token-budget step.

    Prefill runs the fixed ``(1, chunk)`` program of
    ``transformer.prefill_chunk`` — chunk offset and true prompt length are
    traced, so the compile cache holds exactly one step program and one
    finalize program per ``(chunk, batch, policy)`` key regardless of
    traffic shape.  Streaming ``ScoreState`` accumulation makes the final
    eviction identical to monolithic prefill (see tests/test_chunked_
    prefill.py), so serving tokens still match the isolated lockstep
    engine bit-for-bit.  Eviction scores ride the attention kernels
    themselves: cumulative (h2o) prefill takes its per-chunk column-mass
    partials from ``ops.chunk_attention``'s fused second output, and the
    finalize program scores observation windows through the masked
    streaming ``ops.lookahead_score`` primitive — no dense (chunk × buffer)
    probability block exists anywhere in the serving hot path
    (``stats["score_path"]`` records which backend provided the partials).

    The decode loop is unchanged from the bucketed engine: jitted chunks of
    1/2/4/… steps with per-slot cursors and an active mask; a slot that
    finishes mid-chunk has its surplus tokens truncated at collect time
    (greedy decode is prefix-stable) and retires at the chunk boundary.

    With ``prefix_cache`` set (a ``serving.prefix_cache.PrefixCache``),
    admissions consult a radix trie of chunk-boundary ``(KV, ScoreState)``
    snapshots: a hit resumes streaming at the shared prefix's end — the
    cached prefix's attention *and* its eviction-score accumulation are
    both skipped — and a prompt that is exactly a cached prefix admits
    with zero prefill chunks (TTFT ~ one finalize).  Because the resumed
    state is bit-identical to what the request would have streamed itself,
    served tokens and kept sets are unchanged (the differential trace
    suite in tests/test_prefix_cache.py asserts this per policy).
    """

    def __init__(
        self,
        params: dict,
        cfg: ModelConfig,
        config: Optional[ServingConfig] = None,
        *,
        lkv_params: Optional[dict] = None,
        **legacy,
    ):
        if legacy:
            assert config is None, \
                "pass either a ServingConfig or legacy kwargs, not both"
            warnings.warn(
                "ContinuousEngine(**kwargs) is deprecated; build a "
                "serving.config.ServingConfig and pass it as ``config`` "
                "(see the README's Serving API migration table)",
                DeprecationWarning, stacklevel=2)
            config = ServingConfig.from_legacy(**legacy)
        elif config is None:
            config = ServingConfig()
        self.config = config
        policy = config.policy
        num_slots = config.num_slots
        chunk = config.chunking.chunk
        max_context = config.chunking.max_context
        token_budget = config.chunking.token_budget
        decode_chunk = config.chunking.decode_chunk
        max_new_tokens = config.max_new_tokens
        kv_pool = config.kv_pool
        prefix_cache = config.prefix_cache
        mesh = config.mesh
        assert tf.chunkable(cfg), \
            "chunked continuous batching serves attention-only decoder archs"
        assert policy in policies.SINGLE_PASS and policy != "gt_oracle", \
            "multi-pass policies (and gt_oracle) cannot stream; use " \
            "BucketedEngine for those baselines"
        assert policy != "full", \
            "policy 'full' caches whole prompts — its decode cache is not " \
            "shape-uniform; use BucketedEngine"
        self.params, self.cfg = params, cfg
        self.policy = policy
        self.evict = config.evict
        if config.lkv_checkpoint:
            assert lkv_params is None, \
                "pass trained modules either as lkv_params or as " \
                "config.lkv_checkpoint, not both"
            from repro.core.lookahead import load_lookahead_params
            lkv_params = load_lookahead_params(
                config.lkv_checkpoint, cfg, params["layers"])
        self.lkv_params = lkv_params
        # gt_oracle capture hook (the harvest half of the learning loop):
        # called per retired request in ``_collect``
        self.harvest = config.harvest
        # tensor-parallel serving: commit the params to their param_specs
        # shardings (Megatron GQA rules — q on heads, k/v on kv heads,
        # wo and the MLP on the output columns the manual-TP forward reads,
        # over "model") so every jitted program below lowers sharded, and
        # thread the mesh into the chunk / finalize / decode bodies, where
        # attention.py shard_maps the kernels over each shard's local head
        # slice.  Lookahead params are tiny and replicate.
        self.mesh = mesh
        self._mesh_sig = None
        if mesh is not None:
            from jax.sharding import NamedSharding

            from repro.common.sharding import (lkv_specs, mesh_signature,
                                               param_specs)

            self._mesh_sig = mesh_signature(mesh)
            self.params = jax.tree.map(
                lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
                params, param_specs(cfg, mesh))
            if lkv_params is not None:
                self.lkv_params = jax.tree.map(
                    lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
                    lkv_params, lkv_specs(lkv_params))
            if kv_pool is not None:
                assert kv_pool.model_shards == int(mesh.shape["model"]), \
                    "kv pool built for a different mesh: pass the same " \
                    "mesh to KVBlockPool(..., mesh=...)"
        self.num_slots = num_slots
        self.chunk = chunk
        self.max_new_tokens = max_new_tokens
        self.eos_id = config.eos_id
        self.decode_evict = config.decode_evict
        # one margin rule for all engines (serving/config.py): a dense
        # cache keeps ``margin_rows`` append rows beyond the eviction
        # capacity; the paged pool under decode eviction keeps
        # ``interval`` rows — the growth window between evict-and-compact
        # sweeps — instead of the worst-case ``max_new_tokens + 1``
        if kv_pool is not None and self.decode_evict.enabled:
            self.decode_margin = self.decode_evict.interval
        else:
            self.decode_margin = self.decode_evict.margin_rows(max_new_tokens)
        self._chunks = tuple(c for c in self._CHUNK_SIZES if c <= decode_chunk)
        self.token_budget = token_budget or (chunk + num_slots * decode_chunk)
        # the decode-slot capacity must be budget-bound, not context-bound,
        # so context growth never reshapes the live cache
        self.capacity = tf.decode_cache_capacity(
            cfg, policy, self.evict, n_keys_max=1 << 30)
        # context rungs are chunk * 2^k.  All standard traffic (prompts
        # within ``max_context``) shares the single base rung — one
        # compiled chunk shape; longer prompts climb to the smallest rung
        # that fits, so a 16k outlier neither inflates the prefill cost of
        # later short prompts (it gets its own rung) nor adds more than
        # O(log max_len) compiled shapes over a serving lifetime
        self._base_cap = self._rung(max(max_context, self.capacity))
        self._ctx_cap = self._base_cap  # high-water mark (observability)
        self.chunk_cache = ChunkCompileCache(self._build,
                                             mesh_sig=self._mesh_sig)
        self._decode_fns: dict = {}
        self._insert_fn = jax.jit(tf.insert_request_cache)
        # fused sampling epilogue (core/policies.py): temperature / top-k /
        # top-p run inside the jitted decode chunk with per-request keys
        # folded on token position — greedy (None / temperature 0) keeps
        # the bit-exact differential contract
        self.sampling = config.sampling
        self._seeds_h = np.zeros(num_slots, np.int32)
        # prefix-aware KV reuse: chunk-boundary (KV, ScoreState) snapshots
        # shared across requests via a radix trie (serving/prefix_cache.py).
        # A hit resumes mid-prefill with identical streamed state, so the
        # served tokens and kept sets are bit-equal to an uncached serve.
        self.prefix_cache = prefix_cache
        if prefix_cache is not None:
            prefix_cache.bind(chunk=chunk, policy=policy, model=self.params)
        # paged KV memory (serving/kv_pool.py): decode caches live in a
        # shared block pool instead of dense per-slot buffers — eviction
        # frees real device blocks, and admission is gated by free-block
        # count (scheduler admission_gate) rather than slot count alone.
        self.pool = kv_pool
        self._paged_depth = self.capacity + self.decode_margin
        # decode-time streaming eviction on the paged pool: the engine
        # holds the per-slot cumulative attention masses (fed by the fused
        # kernel's second output each decode chunk) and periodically
        # evicts-and-compacts any slot whose cursor reaches the paged
        # depth, freeing the tail blocks back to the pool mid-generation
        self._score_dev: Optional[jnp.ndarray] = None
        if kv_pool is not None:
            if self.decode_evict.enabled:
                assert mesh is None, \
                    "decode-time eviction on the paged pool is " \
                    "single-device (mesh-sharded serving keeps the dense " \
                    "decode_evict path)"
                a = cfg.attn
                self._score_dev = jnp.zeros(
                    (cfg.num_layers, num_slots, self._paged_depth,
                     a.num_kv_heads), jnp.float32)
            self._nb_max = kv_pool.blocks_for(self._paged_depth)
            assert kv_pool.usable_blocks >= self._nb_max + 1, \
                "pool cannot hold even one request's worst-case cache; " \
                "raise --kv-pool-mb or shrink --kv-block-size"
            # host mirrors of the device block tables / cursors — the
            # allocator needs them synchronously, and the advance rule is
            # deterministic (active slots move `steps` per decode chunk),
            # so mirrors never drift from the device state they shadow
            self._table_h = np.zeros((num_slots, self._nb_max), np.int32)
            self._table_dev = _snapshot(self._table_h)
            self._cursor_h = np.zeros(num_slots, np.int32)
            self._npos_h = np.zeros(num_slots, np.int32)
            self._slot_blocks: dict[int, list[int]] = {
                s: [] for s in range(num_slots)}
            self._admit_seq = np.full(num_slots, -1, np.int64)
            self._admit_counter = 0
            # admission policy: with ``reserve_appends`` (default) every
            # admission reserves its worst-case decode-append blocks, so a
            # running request can never be starved by a later one — the
            # vLLM-style watermark.  Without it admission is optimistic
            # (more concurrency when generations end early) and the
            # preempt-to-queue path is the safety valve.
            self.reserve_appends = config.reserve_appends
            self._slot_reserved = np.zeros(num_slots, np.int64)
            bs = kv_pool.block_size
            # block indices only decode appends can touch: [capacity, depth)
            self._append_jbs = list(range(
                self.capacity // bs, (self._paged_depth - 1) // bs + 1))
            if prefix_cache is not None and prefix_cache.pool is not None:
                assert prefix_cache.pool is kv_pool, \
                    "prefix cache bound to a different block pool"
        self.capture_admission = config.capture_admission
        # MoE: each request's chunk programs tally the prompt rows routed
        # to each held expert on the device; the tally is read once, when
        # the request retires (its chunks finished long before)
        self._routed_slot: dict[int, jnp.ndarray] = {}
        # -- observability (repro.obs) ----------------------------------
        # one typed registry per engine replaces the historical ad-hoc
        # ``stats`` dict (kept below as a deprecated read-only view);
        # components mirror their state through callback gauges, the
        # tracer (when attached) receives per-request spans
        self.metrics = MetricsRegistry()
        self.drift = config.drift
        self.trace = None
        self._sync_timers = False
        self._run_started = False  # legacy view: {} before the first run()
        self._last_sched: Optional[SlotScheduler] = None
        self._run_info: dict = {}
        self._uid_seq: dict = {}  # uid -> first admission_seq (replay link)
        self._serve_seq = 0
        self._register_metrics()
        self.chunk_cache.bind_metrics(self.metrics)
        if self.pool is not None:
            self.pool.bind_metrics(self.metrics)
        if self.prefix_cache is not None:
            self.prefix_cache.bind_metrics(self.metrics)
        self.set_trace(config.trace)

    # -- observability ----------------------------------------------------
    def _register_metrics(self) -> None:
        m = self.metrics
        sync_note = (
            "Host perf_counter timer; whether it measures synced execution "
            "(the engine blocks on the chunk's output arrays before "
            "stamping) or async dispatch plus the token sync is recorded "
            "in serving_build's sync_timers key.")
        self._m_prefill_chunks = m.counter(
            "serving_prefill_chunks_total",
            "Prefill chunk programs dispatched.")
        self._m_decode_chunks = m.counter(
            "serving_decode_chunks_total",
            "Slot-batched decode chunk programs dispatched.")
        self._m_decode_steps = m.counter(
            "serving_decode_steps_total",
            "Decode steps advanced (chunk dispatches x chunk length).")
        self._m_decode_seconds = m.counter(
            "serving_decode_seconds_total",
            "Wall seconds spent in decode chunks (the legacy "
            "stats['decode_time_s']). " + sync_note)
        self._m_decode_chunk_hist = m.histogram(
            "serving_decode_chunk_seconds",
            "Per-decode-chunk wall time distribution. " + sync_note)
        self._m_ttft = m.histogram(
            "serving_ttft_seconds",
            "Time to first token per request, from schedulability to the "
            "first emitted token (re-admissions keep the original stamp).")
        self._m_max_prefill_between_decode = m.gauge(
            "serving_max_prefill_between_decode",
            "Worst count of prefill chunks run between two decode chunks "
            "while slots were live — the decode-stall bound the "
            "token-budget step enforces.")
        self._m_max_concurrency = m.gauge(
            "serving_max_concurrency",
            "High-water mark of concurrently running requests.")
        self._m_requests = m.counter(
            "serving_requests_total", "Requests submitted to run().")
        self._m_retired = m.counter(
            "serving_requests_retired_total",
            "Requests retired (finished) across admission and decode.")
        self._m_prefix_hits = m.counter(
            "serving_prefix_hits_total",
            "Admissions resumed from a prefix-cache snapshot.")
        self._m_prefix_misses = m.counter(
            "serving_prefix_misses_total",
            "Admissions that probed the prefix cache and missed.")
        self._m_prefix_tokens_skipped = m.counter(
            "serving_prefix_tokens_skipped_total",
            "Prompt tokens whose prefill (attention and score "
            "accumulation) was skipped via prefix-cache hits.")
        self._m_preemptions = m.counter(
            "serving_preemptions_total",
            "Running requests preempted to the queue (paged pool dry).")
        self._m_admission_blocked = m.counter(
            "serving_admission_blocked_total",
            "Prefilled admissions bounced back to the queue head because "
            "the pool could not place their kept rows.")
        self._m_sweeps = m.counter(
            "serving_decode_evict_sweeps_total",
            "Decode-time evict-and-compact sweeps on the paged pool.")
        if self.cfg.moe is not None:
            self._m_routed = m.counter(
                "moe.routed_rows",
                "Prompt rows of retired requests routed to each held "
                "expert (one count per (row, layer, expert) among the "
                "row's top-k).", labelnames=("expert",))
        self._m_build = m.info(
            "serving_build",
            "Engine build facts: score/decode dispatch path, device mesh, "
            "and whether timers are device-synced (sync_timers).")

    def set_trace(self, trace) -> None:
        """Attach (or detach, with ``None``) an ``obs.trace.TraceRecorder``.

        Resolves the timer-sync mode: ``config.sync_timers`` when set,
        else sync exactly when tracing — so untimed serving keeps the
        async-dispatch pipeline — and propagates the recorder to the
        compile cache (jit_compile events) and the drift monitor."""
        self.trace = trace
        st = self.config.sync_timers
        self._sync_timers = bool(trace is not None if st is None else st)
        if trace is not None:
            trace.sync = self._sync_timers
        self.chunk_cache.trace = trace
        if self.drift is not None:
            self.drift.bind(metrics=self.metrics, trace=trace)

    @property
    def stats(self) -> "_LegacyStatsView":
        """Deprecated: the historical per-run ``stats`` dict, as a
        read-only view computed from the metrics registry.  Read
        ``engine.metrics`` (``value()`` / ``snapshot()`` /
        ``prometheus_text()``) instead."""
        warnings.warn(
            "ContinuousEngine.stats is deprecated; read the typed metrics "
            "registry at engine.metrics (see the README's stats() -> "
            "registry migration table)", DeprecationWarning, stacklevel=2)
        return _LegacyStatsView(self)

    # -- compile-cache bodies ------------------------------------------------
    def _build(self, kind: str, policy: str):
        if kind == "chunk" and self.cfg.moe is not None:
            def fn(params, state, tokens, n_total, routed):
                return tf.prefill_chunk(params, self.cfg, state, tokens,
                                        n_total, policy=policy,
                                        mesh=self.mesh, routed=routed)
        elif kind == "chunk":
            def fn(params, state, tokens, n_total):
                return tf.prefill_chunk(params, self.cfg, state, tokens,
                                        n_total, policy=policy,
                                        mesh=self.mesh)
        else:  # finalize
            def fn(params, lkv, state, n_total, seeds):
                cache = tf.prefill_finalize(
                    params, self.cfg, state, n_total, policy=policy,
                    evict=self.evict, lkv_params=lkv,
                    extra_slots=self.decode_margin, seeds=seeds,
                    mesh=self.mesh,
                )
                if self.decode_evict.enabled:
                    cache = tf.add_decode_eviction_scores(cache)
                return cache

        return fn

    # -- geometry ------------------------------------------------------------
    def _rung(self, need: int) -> int:
        """Smallest chunk * 2^k >= ``need`` (the geometric buffer ladder)."""
        r = self.chunk
        while r < need:
            r *= 2
        return r

    def _request_context(self, n_prompt: int) -> int:
        """KV-buffer depth for one request: the base rung for everything
        within ``max_context``, else the smallest ladder rung that fits the
        prompt + observation rows.  A new rung recompiles the two chunk
        programs once — O(log max_len) compiles over a serving lifetime,
        vs one per bucket for the deprecated ladder."""
        need = policies.chunk_capacity_for(self.cfg, self.policy, n_prompt,
                                           self.chunk)
        cap = max(self._rung(need), self._base_cap)
        self._ctx_cap = max(self._ctx_cap, cap)  # high-water mark
        return cap

    def cache_bytes(self, n_in: int) -> dict:
        """Analytic full-vs-evicted footprint — plus, when serving paged,
        the *actual* pool utilization (blocks used/free, prefix-pinned
        bytes, high-water mark) instead of dense-capacity theory:
        ``evicted`` becomes the measured peak per-request block footprint
        once traffic has been served."""
        out = cache_bytes(self.cfg, self.capacity + self.decode_margin, n_in)
        if self.pool is not None:
            s = self.pool.stats()
            out["pool"] = s
            peak = int(self.metrics.value("serving_max_concurrency"))
            if peak:
                # measured peak per-request footprint (prefix-cache pins
                # are shared capital, not per-request cost)
                decode_hw = max(
                    s["bytes_high_water"] - s["bytes_pinned_prefix"],
                    s["block_bytes"])
                out["evicted"] = decode_hw // peak
                out["ratio"] = out["full"] / max(out["evicted"], 1)
        return out

    def kv_device_bytes(self) -> int:
        """Device bytes the decode KV actually reserves: the whole block
        pool when paged, the dense ``num_slots × (capacity + margin)``
        slot cache otherwise (K+V payload, the paper's headline unit)."""
        if self.pool is not None:
            return self.pool.stats()["bytes_total"]
        a = self.cfg.attn
        per_row = 2 * self.cfg.num_layers * a.kv_dim \
            * jnp.dtype(self.cfg.dtype).itemsize
        return self.num_slots * (self.capacity + self.decode_margin) * per_row

    def warmup(self, prompt_lens=(), batch_sizes=(1,)) -> None:
        """Pre-instantiate the (chunk, batch, policy) compile-cache entries.
        ``prompt_lens`` only pre-sizes the KV-buffer ladder — prompt length
        is a traced argument, not a compile key."""
        for n in prompt_lens:
            self._request_context(n)
        self.chunk_cache.get("chunk", self.chunk, 1, self.policy)
        self.chunk_cache.get("finalize", self.chunk, 1, self.policy)

    # -- serving loop --------------------------------------------------------
    def run(self, requests: list[Request]) -> list[Request]:
        """Serve ``requests`` to completion; returns them in finish order.

        ``arrival_s`` offsets are interpreted on the wall clock relative to
        the start of the call.  Each loop iteration is one token-budget
        step: at most ``plan_step(...)`` prefill chunks of the in-flight
        prompt, then one decode chunk for every live slot — so no live
        slot's decode ever waits longer than one step behind a prompt of
        *any* length.
        """
        sched = SlotScheduler(
            self.num_slots, bucket_for=lambda n: self.chunk,
            max_prefill_batch=1,
            admission_gate=self._admission_gate if self.pool is not None
            else None)
        for r in requests:
            assert r.max_new_tokens <= self.max_new_tokens, \
                "request exceeds the engine's max_new_tokens cache margin"
            sched.submit(r)
        t0 = time.perf_counter()
        if self.pool is not None:
            sched.bind_pool(self.pool)
            live = None  # paged state: block tables + pool, no dense cache
            if self._score_dev is not None:  # clean tallies across runs
                self._score_dev = jnp.zeros_like(self._score_dev)
        else:
            live = tf.init_decode_cache(self.cfg, self.num_slots,
                                        self.capacity + self.decode_margin,
                                        per_slot_cursor=True)
            if self.decode_evict.enabled:
                live = tf.add_decode_eviction_scores(live)
        tok = jnp.zeros((self.num_slots, 1), jnp.int32)
        active = np.zeros(self.num_slots, bool)
        remaining = np.zeros(self.num_slots, np.int64)
        last_emit = np.zeros(self.num_slots, np.float64)
        # fresh collection epoch per run (the historical per-run stats
        # semantics benches rely on: warm up, then time the same engine);
        # callback gauges mirror live component state and are untouched
        self.metrics.reset()
        self._run_started = True
        self._last_sched = sched
        self._uid_seq = {}
        sched.bind_metrics(self.metrics)
        self._run_info = {
            # a local:global pattern's kernel calls take each layer's
            # window as a static value (attention.by_kind), so the
            # kernels serve every layer
            "score_path": ("pallas-fused" if ops.use_pallas()
                           else "jnp-fallback"),
            # which paged_decode_attention tier serves this run
            # (kernel / gather / fallback); "dense" when unpooled — whose
            # per-head masks the Pallas decode kernel does not take, so a
            # kernel backend names the jnp tier it falls to
            "decode_path": (ops.paged_decode_path(self._paged_depth)
                            if self.pool is not None
                            else "dense-jnp" if ops.use_pallas()
                            else "dense"),
            # device mesh this engine serves on (None: single device);
            # bench rows carry it next to decode_path
            "mesh": ({n: int(self.mesh.shape[n])
                      for n in self.mesh.axis_names}
                     if self.mesh is not None else None),
        }
        self._m_build.set(sync_timers=self._sync_timers, **self._run_info)
        self._m_requests.inc(len(requests))

        try:
            self._run_loop(sched, tok, live, active, remaining, last_emit,
                           t0)
        finally:
            if self.pool is not None:
                # a failed run must not leak blocks into the next one (a
                # clean run has already freed every slot at retirement)
                for s in range(self.num_slots):
                    self._free_slot_blocks(s)
        return sched.finished

    def _run_loop(self, sched, tok, live, active, remaining, last_emit,
                  t0) -> None:
        # every host stretch of an iteration runs under a named phase
        # (``_phase``): with a recorder attached, the device trace's idle
        # gaps can be charged to what the host was doing
        phase = self._phase
        pf: Optional[_InflightPrefill] = None
        since_decode = 0
        next_clock = 0.0
        try:
            while sched.has_work() or pf is not None:
                now = time.perf_counter() - t0
                if self.trace is not None and now >= next_clock:
                    self.trace.clock()
                    next_clock = now + CLOCK_EVERY_S
                with phase("schedule"):
                    if pf is None:
                        req = sched.next_request(now)
                        if req is not None:
                            pf = self._begin_prefill(req)
                    if pf is not None:
                        steps = self._pick_chunk(remaining, active) \
                            if active.any() else max(self._chunks)
                        _, n_chunks = plan_step(
                            token_budget=self.token_budget, chunk=self.chunk,
                            n_active=int(active.sum()), decode_steps=steps,
                            prefill_pending=True,
                        )
                if pf is not None:
                    for _ in range(n_chunks):
                        if pf.s < pf.n:  # a full prefix-cache hit has no chunks
                            self._prefill_step(pf)
                            if active.any():  # only live slots can be stalled
                                since_decode += 1
                        if pf.s >= pf.n:
                            with phase("admit"):
                                tok, live = self._admit(
                                    pf, sched, tok, live, active, remaining,
                                    last_emit, t0)
                            pf = None
                            break
                self._m_max_concurrency.max(len(sched.running))
                if active.any():
                    self._m_max_prefill_between_decode.max(since_decode)
                    since_decode = 0
                    steps = self._pick_chunk(remaining, active)
                    if self.pool is not None:
                        if self._score_dev is not None:
                            # decode-time eviction: compact every slot
                            # whose cursor reached the paged depth, then
                            # cap the chunk so no active cursor can
                            # overrun the depth mid-chunk (the sweep
                            # trigger is checked only between chunks)
                            with phase("evict_sweep"):
                                self._decode_evict_sweep(sched, active,
                                                         remaining, last_emit)
                            if not active.any():
                                continue
                            room = int(np.min(
                                (self._paged_depth - self._cursor_h)[active]))
                            steps = max(c for c in self._chunks
                                        if c <= max(room, 1))
                        # grow every live slot's append blocks before the
                        # chunk runs — a missing block would null-route the
                        # appends; preempts the latest admission when dry
                        with phase("append_blocks"):
                            self._ensure_append_blocks(sched, active,
                                                       remaining, last_emit,
                                                       steps)
                        if not active.any():
                            continue  # every live slot was preempted
                        dispatched = active.copy()
                        fn = self._decode_fn_paged(steps)
                        t_dec = time.perf_counter()
                        with phase("decode_chunk", steps=steps,
                                   slots=int(active.sum())):
                            # _snapshot the host mirrors before handing
                            # them to jax: dispatch is async and the
                            # host->device staging of an argument can
                            # happen after this call returns, so a buffer
                            # we mutate in place below (cursor/npos
                            # advance, retirement bookkeeping) would race
                            # the device read
                            if self._score_dev is not None:
                                tok, ptree, toks, self._score_dev = fn(
                                    self.params, tok, self._table_dev,
                                    _snapshot(self._cursor_h),
                                    _snapshot(self._npos_h[:, None]),
                                    self.pool.tree(), _snapshot(active),
                                    _snapshot(self._seeds_h), self._score_dev)
                            else:
                                tok, ptree, toks = fn(
                                    self.params, tok, self._table_dev,
                                    _snapshot(self._cursor_h),
                                    _snapshot(self._npos_h[:, None]),
                                    self.pool.tree(), _snapshot(active),
                                    _snapshot(self._seeds_h))
                            if self._sync_timers:
                                # device-time attribution: block on the
                                # whole output pytree so the span measures
                                # execution, not dispatch
                                jax.block_until_ready((tok, ptree, toks))
                            self.pool.set_tree(ptree)
                            # mirror the device advance rule exactly:
                            # slots active at dispatch move `steps`,
                            # cursors clamp
                            self._cursor_h[dispatched] = np.minimum(
                                self._cursor_h[dispatched] + steps,
                                self._paged_depth)
                            self._npos_h[dispatched] += steps
                    else:
                        fn = self._decode_fn(steps)
                        t_dec = time.perf_counter()
                        with phase("decode_chunk", steps=steps,
                                   slots=int(active.sum())):
                            tok, live, toks = fn(self.params, tok, live,
                                                 jnp.asarray(active),
                                                 jnp.asarray(self._seeds_h))
                            if self._sync_timers:
                                jax.block_until_ready((tok, live, toks))
                    with phase("token_sync"):
                        toks_np = np.asarray(toks)  # device sync: tokens landed
                    dt = time.perf_counter() - t_dec
                    self._m_decode_chunks.inc()
                    self._m_decode_steps.inc(steps)
                    self._m_decode_seconds.inc(dt)
                    self._m_decode_chunk_hist.observe(dt)
                    with phase("collect"):
                        self._collect(toks_np, steps, sched, active,
                                      remaining, last_emit, t0)
                elif pf is None:
                    with phase("wait_arrival"):
                        now2 = time.perf_counter() - t0
                        if sched.has_arrived(now2):
                            if self.pool is not None and not sched.running:
                                # nothing can retire to free blocks:
                                # reclaim prefix-cache pins or fail loudly
                                # instead of spinning on a gated queue head
                                self._reclaim_for_head(sched)
                            continue  # a request is admissible right now
                        nxt = sched.next_arrival()
                        if nxt is None:
                            break  # defensive: nothing queued, nothing running
                        wait = nxt - (time.perf_counter() - t0)
                        if wait > 0:
                            time.sleep(min(wait, 0.05))
        finally:
            # an exception escaping the loop must not leak a trie pin: the
            # cache outlives run() calls, and a leaked ref would make the
            # pinned entry unevictable forever
            if (pf is not None and pf.tip is not None
                    and self.prefix_cache is not None):
                self.prefix_cache.release(pf.tip)
                pf.tip = None

    # -- internals -----------------------------------------------------------
    def _begin_prefill(self, req: Request) -> _InflightPrefill:
        n = len(req.prompt)
        cap = self._request_context(n)
        tr = self.trace
        tid = request_track(req.uid)
        if tr is not None:
            # one "request" span per serve attempt; a re-serve (preemption
            # replay, or an admission bounced off a dry pool) opens a new
            # span whose replay_of carries the original admission_seq —
            # the replay <-> original link the span tests assert
            seq = self._serve_seq
            self._serve_seq += 1
            args = {"uid": req.uid, "admission_seq": seq, "n_prompt": n}
            if req.uid in self._uid_seq:
                args["replay_of"] = self._uid_seq[req.uid]
            else:
                self._uid_seq[req.uid] = seq
            tr.begin("request", tid, **args)
        if self.prefix_cache is not None:
            with self._phase("prefix_probe", tid) as end:
                # only snapshots streamed under this request's KV-buffer
                # rung match — the condition for a bitwise-identical resume
                entry = self.prefix_cache.lookup(req.prompt, capacity=cap)
                hit = entry is not None
                if hit:
                    # materialize before pinning: if it raises there is no
                    # _InflightPrefill yet, so a pin taken here could
                    # never be released by the loop's finally
                    state, logits = self.prefix_cache.materialize(entry, cap)
                    self.prefix_cache.acquire(entry)
                    pf = _InflightPrefill(req, state, n)
                    pf.s = entry.depth
                    pf.logits = logits  # the boundary chunk's logits
                    pf.tip = entry
                    req.cached_prefix_tokens = entry.depth
                    self._m_prefix_hits.inc()
                    self._m_prefix_tokens_skipped.inc(entry.depth)
                else:
                    self._m_prefix_misses.inc()
                if end is not None:
                    end.update(hit=hit, depth=entry.depth if hit else 0)
            if hit:
                return pf
        state = tf.init_chunk_state(self.cfg, self.policy, 1, cap)
        return _InflightPrefill(req, state, n)

    def _prefill_step(self, pf: _InflightPrefill) -> None:
        with self._phase("prefill_chunk", request_track(pf.req.uid),
                         s=pf.s):
            blk = np.zeros((1, self.chunk), np.int32)
            seg = pf.req.prompt[pf.s:pf.s + self.chunk]
            blk[0, :len(seg)] = seg
            fn = self.chunk_cache.get("chunk", self.chunk, 1, self.policy)
            args = (self.params, pf.state, jnp.asarray(blk),
                    jnp.asarray(pf.n, jnp.int32))
            if self.cfg.moe is not None:
                if pf.routed is None:
                    pf.routed = jnp.zeros((self.cfg.moe.held,), jnp.int32)
                pf.state, pf.logits, pf.routed = fn(*args, pf.routed)
            else:
                pf.state, pf.logits = fn(*args)
            if self._sync_timers:
                # device-time attribution: without the block the span measures
                # dispatch only (JAX async dispatch)
                jax.block_until_ready(pf.logits)
            pf.s += self.chunk
            self._m_prefill_chunks.inc()
            # cache the boundary just crossed (whole-chunk prefixes only — a
            # partial final chunk contains pad rows and is never cacheable)
            if self.prefix_cache is not None and pf.s <= pf.n:
                entry = self.prefix_cache.insert(
                    pf.req.prompt[:pf.s], state=pf.state, logits=pf.logits,
                    parent=pf.tip)
                if entry is not None:
                    self.prefix_cache.acquire(entry)
                    if pf.tip is not None:  # the parent link keeps it alive now
                        self.prefix_cache.release(pf.tip)
                    pf.tip = entry

    def _admit(self, pf, sched, tok, live, active, remaining, last_emit, t0):
        r = pf.req
        tr = self.trace
        tid = request_track(r.uid)
        fn = self.chunk_cache.get("finalize", self.chunk, 1, self.policy)
        with self._phase("finalize", tid):
            cache = fn(self.params, self.lkv_params, pf.state,
                       jnp.asarray(pf.n, jnp.int32), _request_seeds([r]))
            if self._sync_timers:
                jax.block_until_ready(cache)
        if self.prefix_cache is not None and pf.tip is not None:
            self.prefix_cache.release(pf.tip)
            pf.tip = None
        with self._phase("admit_sync"):
            if self.capture_admission:
                r.admission_cache = {
                    key: np.asarray(val) for key, val in cache["attn"].items()
                    if key in ("mask", "pos", "score")
                }
            pf.logits.block_until_ready()
        if self.pool is not None:
            with self._phase("place"):
                slot = self._paged_place(sched, r, cache)
            if slot is None:
                # the gate's headroom was eaten by running slots' appends
                # during this prefill: back to the queue head, re-prefill
                # when blocks free (FCFS order and served tokens unchanged
                # — greedy decode is deterministic)
                self._m_admission_blocked.inc()
                sched.push_front(r)
                if tr is not None:
                    tr.end("request", tid, outcome="admission_blocked")
                return tok, live
        else:
            with self._phase("place"):
                slot = sched.place(r)
                live = self._insert_fn(live, cache, slot)
        now = time.perf_counter() - t0
        self._seeds_h[slot] = r.eviction_seed
        if pf.routed is not None:
            self._routed_slot[slot] = pf.routed
        with self._phase("first_token"):
            first = self._first_token(pf.logits, r.eviction_seed, pf.n)
            tok = tok.at[slot, 0].set(first)
        r.out_tokens = [first]
        if tr is not None:
            tr.instant("first_token", tid, token=first)
        if r.first_token_s is None:
            # a re-admitted (preempted) request keeps its original stamp:
            # the client received its first token then, and the replayed
            # tokens are bit-identical — the preemption shows up in
            # max_gap_s / tpot_s, where the stall honestly belongs
            r.first_token_s = now
            r.ttft_s = now - r.enqueue_s
            self._m_ttft.observe(r.ttft_s)
        if r.preempt_emit_s is not None:
            # the client-visible stall spans preemption to this re-emit
            r.max_gap_s = max(r.max_gap_s, now - r.preempt_emit_s)
            r.preempt_emit_s = None
        last_emit[slot] = now
        if first == self.eos_id or r.max_new_tokens <= 1:
            sched.retire(r, now=now)
            active[slot] = False
            self._on_retire(slot, r)
            self._release_slot(slot)
            self._m_retired.inc()
            if tr is not None:
                tr.instant("retire", tid, tokens=len(r.out_tokens))
                tr.end("request", tid, outcome="done")
        else:
            active[slot] = True
            remaining[slot] = r.max_new_tokens - 1
            if tr is not None:
                tr.begin("decode", tid)
        return tok, live

    # -- paged-KV internals (serving/kv_pool.py) --------------------------
    #
    # The decode cache of every live slot is a run of pool blocks behind a
    # per-slot block table; the *logical* layout is bit-identical to the
    # dense engine's (kept rows at [0, capacity), appends from `capacity`),
    # with never-valid gaps and not-yet-grown tails backed by the null
    # block.  Admission writes only the blocks that cover actual kept rows
    # — that is where eviction quality becomes freed memory — and append
    # blocks grow one at a time ahead of each decode chunk.

    def _request_blocks(self, n_prompt: int) -> tuple[int, int]:
        """(worst-case kept-data blocks, append blocks beyond them) for a
        prompt of ``n_prompt`` tokens — the admission cost model.  Short
        prompts and tight budgets need fewer data blocks than the dense
        engine's uniform ``capacity + margin`` rows: that delta is the
        concurrency eviction buys.  Under decode-time eviction the
        worst case shrinks again — the slot's whole window is
        ``capacity + interval`` rows instead of ``capacity +
        max_new_tokens + 1`` — but sweeps eventually materialize *every*
        block of it (gap blocks included), so the append promise is the
        full window minus the admitted data blocks."""
        data = self.pool.blocks_for(min(n_prompt, self.capacity))
        if self._score_dev is not None:
            return data, self._nb_max - data
        appends = sum(1 for jb in self._append_jbs if jb >= data)
        return data, appends

    def _admission_gate(self, req: Request) -> bool:
        """Free-block admission: the FCFS head admits only when the pool
        can cover its worst-case kept rows — plus, under
        ``reserve_appends``, its whole future decode growth (no-preempt
        guarantee); optimistic admission asks only one append block.
        Blocks evictable from the prefix cache count as free — the engine
        reclaims them on demand."""
        data, appends = self._request_blocks(len(req.prompt))
        need = data + (appends if self.reserve_appends else 1)
        free = self.pool.available_blocks()
        if self.prefix_cache is not None and self.prefix_cache.pool is not None:
            free += self.prefix_cache.evictable_pool_blocks()
        return free >= need

    def _alloc_blocks(self, n: int) -> Optional[np.ndarray]:
        """Pool allocation that reclaims prefix-cache blocks on demand:
        live requests outrank cached prefixes."""
        ids = self.pool.alloc(n)
        if ids is None and (self.prefix_cache is not None
                            and self.prefix_cache.pool is not None):
            # shortfall vs *available* blocks: ordinary allocs may not dip
            # into append reservations, so reclaiming only down to the
            # free-list size would under-evict and leave alloc failing
            if self.prefix_cache.evict_pool_blocks(
                    n - self.pool.available_blocks()):
                ids = self.pool.alloc(n)
        return ids

    def _reserve_blocks(self, n: int) -> bool:
        """`pool.reserve` with the same reclaim-from-prefix-cache fallback
        as `_alloc_blocks`.  Without it the admission gate (which counts
        evictable prefix blocks as free) and a failing reserve would agree
        to disagree forever: the gate re-admits, the reserve re-fails with
        the pool state unchanged — a livelock."""
        if self.pool.reserve(n):
            return True
        if self.prefix_cache is not None and self.prefix_cache.pool is not None:
            if self.prefix_cache.evict_pool_blocks(
                    n - self.pool.available_blocks()):
                return self.pool.reserve(n)
        return False

    def _paged_place(self, sched, r: Request, cache: dict) -> Optional[int]:
        """Write the admitted cache's kept rows into freshly allocated
        blocks and point a slot's table at them.  Returns the slot, or
        None when the pool cannot cover the kept rows right now."""
        mask = cache["attn"]["mask"]  # (L, 1, C, KV)
        C = mask.shape[2]
        with self._phase("admit_sync"):
            rows = jnp.arange(C, dtype=jnp.int32)[None, None, :, None]
            used = int(jnp.max(jnp.where(mask, rows, 0))) + 1
        ids = self._alloc_blocks(self.pool.blocks_for(used))
        if ids is None:
            return None
        if self._score_dev is not None:
            # sweeps compact through every block of [0, depth), so gap
            # blocks below the append window count toward the promise too
            outstanding = self._nb_max - len(ids)
        else:
            outstanding = sum(1 for jb in self._append_jbs if jb >= len(ids))
        if self.reserve_appends and not self._reserve_blocks(outstanding):
            self.pool.free(ids)  # promise can't be kept: don't admit
            return None
        self.pool.write_cache(cache["attn"], ids)
        slot = sched.place(r)
        self._slot_reserved[slot] = outstanding if self.reserve_appends else 0
        self._admit_seq[slot] = self._admit_counter
        self._admit_counter += 1
        self._slot_blocks[slot] = [int(b) for b in ids]
        self._table_h[slot] = 0
        self._table_h[slot, :len(ids)] = ids
        self._table_dev = _snapshot(self._table_h)
        self._cursor_h[slot] = self.capacity  # appends start where dense do
        with self._phase("admit_sync"):
            self._npos_h[slot] = int(cache["next_pos"][0, 0])
        if self._score_dev is not None:
            # arm the slot's cumulative tallies exactly as the dense
            # engine's add_decode_eviction_scores seeds its score field
            # (finalize already attached it: valid kept rows = unit mass)
            sc = cache["attn"]["score"]  # (L, 1, C, KV)
            assert sc.shape[2] == self._paged_depth, \
                "admitted cache depth must match the paged window"
            self._seed_score(slot, sc)
        return slot

    def _decode_fn_paged(self, steps: int):
        scored = self._score_dev is not None
        fn = self._decode_fns.get(("paged", steps, scored))
        if fn is None:
            depth = self._paged_depth
            sampling = self.sampling
            mesh = self.mesh

            if scored:
                # the score buffer rides *inside* the pool dict: the
                # transformer layer scan slices its (L, S, depth, KV)
                # leaf per layer like every other pool leaf, and the
                # attention step adds the fused kernel's masses to it —
                # no signature changes anywhere below decode_chunk
                def body(params, tok, table, cursor, next_pos, pool,
                         active, seeds, score):
                    pool = dict(pool, score=score)
                    cache = {"attn": {"table": table}, "pool": pool,
                             "cursor": cursor, "next_pos": next_pos}
                    last, cache, toks = policies.decode_chunk(
                        params, self.cfg, tok, cache, steps, active=active,
                        paged_depth=depth, sampling=sampling, seeds=seeds,
                        mesh=mesh)
                    newpool = dict(cache["pool"])
                    newscore = newpool.pop("score")
                    return last, newpool, toks, newscore
            else:
                def body(params, tok, table, cursor, next_pos, pool,
                         active, seeds):
                    cache = {"attn": {"table": table}, "pool": pool,
                             "cursor": cursor, "next_pos": next_pos}
                    last, cache, toks = policies.decode_chunk(
                        params, self.cfg, tok, cache, steps, active=active,
                        paged_depth=depth, sampling=sampling, seeds=seeds,
                        mesh=mesh)
                    return last, cache["pool"], toks

            fn = jax.jit(body)
            self._decode_fns[("paged", steps, scored)] = fn
        return fn

    def _seed_score(self, slot: int, score: jnp.ndarray) -> None:
        """Write an admitted request's initial cumulative-score plane
        ((L, 1, depth, KV), from ``add_decode_eviction_scores``) into the
        engine's per-slot score buffer."""
        fn = self._decode_fns.get("seed_score")
        if fn is None:
            def body(buf, sc, slot):
                return buf.at[:, slot].set(sc[:, 0].astype(jnp.float32))

            fn = jax.jit(body)
            self._decode_fns["seed_score"] = fn
        self._score_dev = fn(self._score_dev, score,
                             jnp.asarray(slot, jnp.int32))

    def _decode_evict_sweep(self, sched, active, remaining,
                            last_emit) -> None:
        """Evict-and-compact every live slot whose cursor reached the
        paged depth: run the jitted ``paged_sweep`` (keep the
        ``capacity`` heaviest rows, compact them into the head blocks),
        free the tail blocks back to the pool mid-generation, and reset
        the slot's cursor to ``capacity``.  Table gaps below the kept
        window (short admissions never allocated them) are materialized
        first — the compaction scatter needs real blocks to land in."""
        bs = self.pool.block_size
        nb = self.pool.blocks_for(self._paged_depth)
        nb_keep = self.pool.blocks_for(self.capacity)
        for slot in np.nonzero(active)[0].tolist():
            if not active[slot]:
                continue  # preempted by an earlier slot's gap fill
            if int(self._cursor_h[slot]) < self._paged_depth:
                continue
            aborted = False
            for jb in range(nb):
                if self._table_h[slot, jb] != 0:
                    continue
                if self._slot_reserved[slot] > 0:
                    ids = self.pool.alloc(1, from_reserved=True)
                    assert ids is not None  # reserves stay on the free list
                    self._slot_reserved[slot] -= 1
                else:
                    ids = self._alloc_blocks(1)
                while ids is None:
                    victim = self._latest_admitted_active(active)
                    assert victim is not None, "pool exhausted with no slots"
                    self._preempt(victim, sched, active, remaining,
                                  last_emit)
                    if not active[slot]:
                        break  # this slot was its own latest admission
                    ids = self._alloc_blocks(1)
                if not active[slot]:
                    aborted = True
                    break
                # a reallocated block may carry stale validity rows; the
                # sweep *gathers* through the table before its scatter
                # overwrites them, so invalidate up front
                self.pool.zero_mask(ids)
                self._table_h[slot, jb] = int(ids[0])
                self._slot_blocks[slot].append(int(ids[0]))
            if aborted:
                continue
            with self._phase("paged_sweep",
                             request_track(sched.running[slot].uid)) as end:
                self._table_dev = _snapshot(self._table_h)
                ptree, self._score_dev = paged_sweep(
                    self.pool.tree(), self._score_dev, self._table_dev,
                    jnp.asarray(slot, jnp.int32), capacity=self.capacity,
                    depth=self._paged_depth, block_size=bs, nb_keep=nb_keep)
                self.pool.set_tree(ptree)
                # the compacted tail is dead weight now: free it (the
                # whole point — blocks return to the pool mid-generation)
                # and re-promise the same count for the next growth window
                freed = [int(self._table_h[slot, jb]) for jb in
                         range(nb_keep, nb)]
                self.pool.free_run(freed)
                fs = set(freed)
                self._slot_blocks[slot] = [
                    b for b in self._slot_blocks[slot] if b not in fs]
                self._table_h[slot, nb_keep:nb] = 0
                if self.reserve_appends:
                    ok = self.pool.reserve(len(freed))
                    assert ok  # the freed blocks are on the free list
                    self._slot_reserved[slot] += len(freed)
                self._cursor_h[slot] = self.capacity
                self._table_dev = _snapshot(self._table_h)
                if self._sync_timers:
                    jax.block_until_ready(self._score_dev)
                if end is not None:
                    end["blocks_freed"] = len(freed)
            self._m_sweeps.inc()

    def routed_rows(self) -> dict:
        """``moe.routed_rows`` so far: {global expert id: rows}."""
        m = self.cfg.moe
        ids = range(m.first_expert, m.first_expert + m.held)
        return {e: int(self._m_routed.value(expert=str(e))) for e in ids}

    def _on_retire(self, slot: int, req: Request) -> None:
        routed = self._routed_slot.pop(slot, None)
        if routed is not None:
            for i, n in enumerate(np.asarray(routed).tolist()):
                self._m_routed.inc(
                    n, expert=str(self.cfg.moe.first_expert + i))
        if not (self.capture_admission and self.pool is not None):
            return
        fn = self._decode_fns.get("retire_gather")
        if fn is None:
            nb = self._nb_max
            depth = self._paged_depth

            def body(pos, mask, row, horizon):
                def dense(leaf):  # (L, NB, KV, bs) -> (L, depth, KV)
                    return from_blocks(leaf[:, row[:nb]])[:, :depth]

                p = dense(pos)
                # clip at the emitted-token horizon: decode chunks may
                # overshoot a finishing request (surplus tokens are
                # truncated at collect time) and whether those surplus
                # rows fit the cache depends only on the margin, so they
                # are not part of the request's kept set
                return p, dense(mask) & (p < horizon)

            fn = jax.jit(body)
            self._decode_fns["retire_gather"] = fn
        t = self.pool.tree()
        horizon = len(req.prompt) + max(len(req.out_tokens) - 1, 0)
        pos, mask = fn(t["pos"], t["mask"], _snapshot(self._table_h[slot]),
                       jnp.asarray(horizon, jnp.int32))
        req.retirement_cache = {"pos": np.asarray(pos),
                                "mask": np.asarray(mask)}

    def _first_token(self, logits, seed: int, pos: int) -> int:
        """The admission token, sampled with the same fused-epilogue logic
        (and the same (seed, position) key) the decode chunks use — or
        host argmax when greedy."""
        s = self.sampling
        if s is None or s.temperature <= 0.0:
            return int(jnp.argmax(logits[0]))
        fn = self._decode_fns.get("first")
        if fn is None:
            def body(logits, seed, pos):
                keys = policies.fold_keys(seed[None], pos[None])
                return policies.sample_logits(
                    logits, keys, temperature=s.temperature,
                    top_k=s.top_k, top_p=s.top_p)[0]

            fn = jax.jit(body)
            self._decode_fns["first"] = fn
        return int(fn(logits, jnp.asarray(seed, jnp.int32),
                      jnp.asarray(pos, jnp.int32)))

    def _free_slot_blocks(self, slot: int) -> None:
        ids = self._slot_blocks[slot]
        if ids:
            self.pool.free(ids)
            self._slot_blocks[slot] = []
        if self._slot_reserved[slot]:
            self.pool.unreserve(int(self._slot_reserved[slot]))
            self._slot_reserved[slot] = 0
        self._table_h[slot] = 0

    def _release_slot(self, slot: int) -> None:
        if self.pool is not None:
            # the device table row is stale until the next admission
            # overwrites it — harmless: the slot is inactive, its reads
            # are discarded and its writes are null-routed
            self._free_slot_blocks(slot)

    def _latest_admitted_active(self, active) -> Optional[int]:
        live = np.nonzero(active)[0]
        if len(live) == 0:
            return None
        return int(live[np.argmax(self._admit_seq[live])])

    def _preempt(self, slot: int, sched, active, remaining,
                 last_emit) -> None:
        """Preempt-to-queue: abandon a running slot's decode state, free
        its blocks, and push its request back to the FCFS head for a
        from-scratch re-serve (deterministic greedy decode ⇒ identical
        tokens).  The original ``ttft_s`` / ``first_token_s`` stamps are
        kept — the client already received those tokens and the replay is
        bit-identical — so the stall lands in ``max_gap_s``/``tpot_s``
        (see ``_admit``)."""
        r = sched.running[slot]
        tr = self.trace
        if tr is not None:
            tid = request_track(r.uid)
            tr.instant("preempt", tid, emitted=len(r.out_tokens))
            tr.end("decode", tid)
            tr.end("request", tid, outcome="preempted")
        sched.requeue(r)
        r.out_tokens = []  # rebuilt bit-identically by the re-serve
        r.preempt_emit_s = last_emit[slot]  # the stall starts here
        r.cached_prefix_tokens = 0
        r.admission_cache = None
        self._routed_slot.pop(slot, None)  # the re-serve counts again
        self._free_slot_blocks(slot)
        active[slot] = False
        remaining[slot] = 0
        self._m_preemptions.inc()

    def _ensure_append_blocks(self, sched, active, remaining, last_emit,
                              steps: int) -> None:
        """Allocate the append blocks every live slot needs for the next
        ``steps`` decode tokens.  When the pool runs dry, the latest
        admission is preempted to the queue (LIFO victims preserve FCFS
        finish order) until the remaining slots fit — the engine-sizing
        assert guarantees a lone request always fits."""
        bs = self.pool.block_size
        changed = False
        for slot in np.nonzero(active)[0].tolist():
            if not active[slot]:
                continue  # preempted by an earlier slot's reclaim
            cur = int(self._cursor_h[slot])
            last = min(cur + steps - 1, self._paged_depth - 1)
            for jb in range(cur // bs, last // bs + 1):
                if self._table_h[slot, jb] != 0:
                    continue
                if self._slot_reserved[slot] > 0:
                    # redeem this slot's admission-time promise — cannot
                    # fail (the pool keeps reserved blocks on the free
                    # list), which is the no-preempt guarantee
                    ids = self.pool.alloc(1, from_reserved=True)
                    assert ids is not None
                    self._slot_reserved[slot] -= 1
                else:
                    ids = self._alloc_blocks(1)
                while ids is None:
                    victim = self._latest_admitted_active(active)
                    assert victim is not None, "pool exhausted with no slots"
                    self._preempt(victim, sched, active, remaining,
                                  last_emit)
                    changed = True
                    if not active[slot]:
                        break  # this slot was its own latest admission
                    ids = self._alloc_blocks(1)
                if not active[slot]:
                    break
                # a reallocated block may carry its previous owner's stale
                # validity rows — invalidate before the table exposes it
                self.pool.zero_mask(ids)
                self._table_h[slot, jb] = int(ids[0])
                self._slot_blocks[slot].append(int(ids[0]))
                changed = True
        if changed:
            self._table_dev = _snapshot(self._table_h)

    def _reclaim_for_head(self, sched) -> None:
        """Nothing is running yet the queue head stays gated: every
        missing block is pinned by the prefix cache.  Reclaim until the
        gate passes, or fail with a sizing error instead of spinning."""
        while True:
            if not sched._queue:
                return
            if self._admission_gate(sched._queue[0]):
                return
            pc = self.prefix_cache
            if pc is None or pc.pool is None or not pc.evict_pool_blocks(1):
                raise RuntimeError(
                    "kv pool too small for the queue head even with the "
                    "prefix cache emptied; raise --kv-pool-mb")


class BucketedEngine(_SlotDecodeMixin):
    """Deprecated continuous-batching engine with pad-to-bucket prefill.

    A slot-batched decode loop (``_SlotDecodeMixin``) fed by per-bucket
    *monolithic* prefill: one compile per ``(bucket, batch, policy,
    padded)`` key, prompts beyond the largest bucket escalate to
    power-of-two buckets, and every live decode slot stalls for the whole
    prefill of an admitted prompt.  Kept (with its exactness guarantees)
    as the benchmark baseline the chunked engine is measured against.

    Exactness: tokens match isolated lockstep serving bit-for-bit for
    ``lookaheadkv`` and the position policies even when prompts are padded
    to their bucket (padded rows are masked everywhere — see
    ``transformer.prefill``'s ``prompt_lens``).  The snapkv-family
    baselines are exact when a prompt fills its bucket and approximate
    otherwise (their sliding observation windows overlap the padding).
    Multi-pass policies (laq/speckv) are grouped by exact prompt length
    instead of bucketed.
    """

    def __init__(
        self,
        params: dict,
        cfg: ModelConfig,
        *,
        policy: str = "lookaheadkv",
        evict: Optional[EvictionConfig] = None,
        lkv_params: Optional[dict] = None,
        draft_params: Optional[dict] = None,
        draft_cfg: Optional[ModelConfig] = None,
        num_slots: int = 4,
        buckets: tuple = DEFAULT_BUCKETS,
        max_prefill_batch: Optional[int] = None,
        max_new_tokens: int = 64,  # per-request cap (sizes the cache margin)
        eos_id: int = 0,
        decode_evict: bool = False,
        decode_chunk: int = 8,
    ):
        warnings.warn(
            "BucketedEngine (pad-to-bucket prefill) is deprecated; serve "
            "through the chunked ContinuousEngine", DeprecationWarning,
            stacklevel=2)
        assert cfg.uses_attention and not cfg.uses_ssm \
            and not cfg.is_encoder_decoder, \
            "continuous batching serves attention-only archs"
        assert policy != "gt_oracle", "gt_oracle needs the future; not servable"
        self.params, self.cfg = params, cfg
        self.policy = policy
        self.evict = evict if evict is not None else EvictionConfig()
        self.lkv_params = lkv_params
        self.draft_params, self.draft_cfg = draft_params, draft_cfg
        self.num_slots = num_slots
        self.buckets = tuple(sorted(buckets))
        self.max_prefill_batch = max_prefill_batch or num_slots
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.decode_evict = DecodeEvictionConfig.coerce(decode_evict)
        self.decode_margin = self.decode_evict.margin_rows(max_new_tokens)
        self._chunks = tuple(c for c in self._CHUNK_SIZES if c <= decode_chunk)
        # multi-pass policies draft with the compressed cache; their prefill
        # can't mask padding, so their groups use exact prompt lengths
        self._exact_only = policy in policies.MULTI_PASS
        self.capacity = tf.decode_cache_capacity(
            cfg, policy, self.evict, n_keys_max=max(self.buckets))
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            self.prefill_cache = PrefillCompileCache(self._build_prefill)
        self._decode_fns: dict = {}
        self._insert_fn = jax.jit(tf.insert_request_cache)
        self.sampling = None  # the deprecated baseline decodes greedily
        self._seeds_h = np.zeros(num_slots, np.int32)
        self.stats: dict = {}

    # -- compile-cache bodies ------------------------------------------------
    def _build_prefill(self, policy: str, padded: bool):
        def fn(params, lkv, tokens, lens, seeds):
            res = policies.run_eviction(
                policy, params, self.cfg, tokens, evict=self.evict,
                lkv_params=lkv, draft_params=self.draft_params,
                draft_cfg=self.draft_cfg, extra_slots=self.decode_margin,
                prompt_lens=lens if padded else None, seeds=seeds,
            )
            if self.decode_evict.enabled:
                res = res._replace(
                    cache=tf.add_decode_eviction_scores(res.cache))
            return res

        return fn

    # -- geometry ------------------------------------------------------------
    def _bucket(self, n: int) -> int:
        if self._exact_only:
            return n
        b = _bucket_for(n, self.buckets)
        if self.policy == "full" and b > max(self.buckets):
            raise ValueError(
                f"policy 'full' caches whole prompts; len {n} exceeds the "
                f"largest bucket {max(self.buckets)}")
        return b

    def cache_bytes(self, n_in: int) -> dict:
        return cache_bytes(self.cfg, self.capacity + self.decode_margin, n_in)

    def kv_device_bytes(self) -> int:
        """K+V bytes of the dense live slot cache (see the paged engine's
        pool-aware counterpart)."""
        a = self.cfg.attn
        per_row = 2 * self.cfg.num_layers * a.kv_dim \
            * jnp.dtype(self.cfg.dtype).itemsize
        return self.num_slots * (self.capacity + self.decode_margin) * per_row

    def warmup(self, prompt_lens, batch_sizes=(1,)) -> None:
        """Pre-build compile-cache entries for expected traffic shapes."""
        keys = []
        for n in prompt_lens:
            b = self._bucket(n)
            for nb in batch_sizes:
                nb = _batch_bucket(nb, self.max_prefill_batch)
                keys.append((b, nb, self.policy, n != b))
        self.prefill_cache.warm(keys)

    # -- serving loop --------------------------------------------------------
    def run(self, requests: list[Request]) -> list[Request]:
        """Serve ``requests`` to completion; returns them in finish order.

        ``arrival_s`` offsets are interpreted on the wall clock relative to
        the start of the call: a request is schedulable once the engine
        clock passes its arrival.  All timing fields (``ttft_s``,
        ``tpot_s``, ``finish_s``) are per-request, measured on that clock.
        """
        sched = SlotScheduler(self.num_slots, bucket_for=self._bucket,
                              max_prefill_batch=self.max_prefill_batch)
        for r in requests:
            assert r.max_new_tokens <= self.max_new_tokens, \
                "request exceeds the engine's max_new_tokens cache margin"
            sched.submit(r)
        t0 = time.perf_counter()
        live = tf.init_decode_cache(self.cfg, self.num_slots,
                                    self.capacity + self.decode_margin,
                                    per_slot_cursor=True)
        if self.decode_evict.enabled:
            live = tf.add_decode_eviction_scores(live)
        tok = jnp.zeros((self.num_slots, 1), jnp.int32)
        active = np.zeros(self.num_slots, bool)
        remaining = np.zeros(self.num_slots, np.int64)
        last_emit = np.zeros(self.num_slots, np.float64)
        # deprecated engine, legacy dict stats.  ``decode_time_s`` is a
        # host timer stamped after the np.asarray sync on the sampled
        # tokens only — under JAX async dispatch it bounds execution
        # loosely (dispatch + token materialization), unlike the chunked
        # engine's sync_timers-gated metrics (repro.obs)
        self.stats = {"decode_chunks": 0, "decode_steps": 0,
                      "decode_time_s": 0.0, "decode_path": "dense"}

        while sched.has_work():
            # admission: fill freed slots from the queue, one bucket group
            # per prefill program.  ``now`` refreshes inside the loop so
            # requests that arrived during a (multi-second, possibly
            # compile-including) prefill are admissible immediately.
            while True:
                now = time.perf_counter() - t0
                group = sched.next_prefill_group(now)
                if not group:
                    break
                tok, live = self._admit(group, sched, tok, live, active,
                                        remaining, last_emit, t0)
            if active.any():
                steps = self._pick_chunk(remaining, active)
                fn = self._decode_fn(steps)
                t_dec = time.perf_counter()
                tok, live, toks = fn(self.params, tok, live,
                                     jnp.asarray(active),
                                     jnp.asarray(self._seeds_h))
                toks_np = np.asarray(toks)  # device sync: tokens landed
                self.stats["decode_chunks"] += 1
                self.stats["decode_steps"] += steps
                self.stats["decode_time_s"] += time.perf_counter() - t_dec
                self._collect(toks_np, steps, sched, active,
                              remaining, last_emit, t0)
            else:
                nxt = sched.next_arrival()
                if nxt is None:
                    break  # defensive: nothing queued, nothing running
                wait = nxt - (time.perf_counter() - t0)
                if wait > 0:
                    time.sleep(min(wait, 0.05))
        return sched.finished

    # -- internals -----------------------------------------------------------
    def _admit(self, group, sched, tok, live, active, remaining, last_emit,
               t0):
        lens = [len(r.prompt) for r in group]
        bucket = self._bucket(max(lens))
        padded = any(n != bucket for n in lens)
        nb = _batch_bucket(len(group), self.max_prefill_batch)
        tokens, lens_arr = _pad_to_bucket([r.prompt for r in group], bucket,
                                          nb)
        seeds = np.zeros((nb,), np.int32)
        seeds[:len(group)] = [r.eviction_seed for r in group]
        fn = self.prefill_cache.get(bucket, nb, self.policy, padded)
        res = fn(self.params, self.lkv_params, jnp.asarray(tokens),
                 jnp.asarray(lens_arr), jnp.asarray(seeds))
        res.logits.block_until_ready()
        now = time.perf_counter() - t0
        first = np.asarray(jnp.argmax(res.logits, -1).astype(jnp.int32))
        for i, r in enumerate(group):
            slot = sched.place(r)
            req_cache = tf.extract_request_cache(res.cache, i)
            live = self._insert_fn(live, req_cache, slot)
            tok = tok.at[slot, 0].set(int(first[i]))
            r.out_tokens = [int(first[i])]
            r.first_token_s = now
            r.ttft_s = now - r.enqueue_s
            last_emit[slot] = now
            if r.out_tokens[-1] == self.eos_id or r.max_new_tokens <= 1:
                sched.retire(r, now=now)
                active[slot] = False
            else:
                active[slot] = True
                remaining[slot] = r.max_new_tokens - 1
        return tok, live
