"""Grouped-query attention block with RoPE / M-RoPE, QKV bias, sliding-window
and local:global patterns, lookahead-LoRA hooks, KV caches, and the
importance-score capture path used by the eviction policies.

Single-layer params (stacked along L by transformer.py):

    {"wq": (D, H*hd), "wk": (D, KV*hd), "wv": (D, KV*hd), "wo": (H*hd, D),
     ["bq","bk","bv"]: biases when cfg.attn.qkv_bias}
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.common.config import AttentionConfig, ModelConfig
from repro.common.sharding import column_split, manual_tp
from repro.kernels import ops
from repro.models import rope
from repro.models.layers import dense_init, linear


class AttnInputs(NamedTuple):
    """Per-call dynamic context for the attention block."""

    positions: jnp.ndarray  # (B, S) absolute positions of the q rows
    mrope_positions: Optional[jnp.ndarray] = None  # (3, B, S)
    lookahead_mask: Optional[jnp.ndarray] = None  # (B, S, 1) selective-LoRA mask
    # decode-time cache (see transformer.make_attn_cache): dict with
    # k: (B, C, KV, hd), v: idem, pos: (B, C), mask: (B, C)
    cache: Optional[dict] = None
    cache_cursor: Optional[jnp.ndarray] = None  # scalar int32 insert index
    # production mesh for shard_map'd decode attention (split-cache path)
    mesh: Optional[object] = None


def init(key, cfg: ModelConfig, *, cross: bool = False) -> dict:
    a = cfg.attn
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, a.q_dim, dtype),
        "wk": dense_init(ks[1], cfg.d_model, a.kv_dim, dtype),
        "wv": dense_init(ks[2], cfg.d_model, a.kv_dim, dtype),
        "wo": dense_init(ks[3], a.q_dim, cfg.d_model, dtype),
    }
    if a.qkv_bias and not cross:
        p["bq"] = jnp.zeros((a.q_dim,), dtype)
        p["bk"] = jnp.zeros((a.kv_dim,), dtype)
        p["bv"] = jnp.zeros((a.kv_dim,), dtype)
    return p


def _lora_for(lora: Optional[dict], name: str) -> Optional[dict]:
    if lora is None:
        return None
    return lora.get(name)


def qkv(
    p: dict,
    a: AttentionConfig,
    h: jnp.ndarray,  # (B, S, D)
    inp: AttnInputs,
    *,
    lora: Optional[dict] = None,
    lora_scale: float = 1.0,
    rotary: bool = True,
    is_global: jnp.ndarray | bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Project + rotate with the rotary of the layer's kind
    (``AttentionConfig.rotary``).  Returns q (B,S,H,hd), k/v (B,S,KV,hd)."""
    B, S, _ = h.shape
    lm = inp.lookahead_mask
    smesh = model_shard_mesh(inp.mesh, a)
    if smesh is not None:
        q, k, v = _sharded_qkv_project(p, h, lm, lora, lora_scale, smesh)
    else:
        q = linear(h, p["wq"], p.get("bq"), lora=_lora_for(lora, "wq"),
                   lora_mask=lm, lora_scale=lora_scale)
        k = linear(h, p["wk"], p.get("bk"), lora=_lora_for(lora, "wk"),
                   lora_mask=lm, lora_scale=lora_scale)
        v = linear(h, p["wv"], p.get("bv"), lora=_lora_for(lora, "wv"),
                   lora_mask=lm, lora_scale=lora_scale)
    q = q.reshape(B, S, a.num_heads, a.head_dim)
    k = k.reshape(B, S, a.num_kv_heads, a.head_dim)
    v = v.reshape(B, S, a.num_kv_heads, a.head_dim)
    q, k, v = (pin_heads(q, smesh), pin_heads(k, smesh),
               pin_heads(v, smesh))
    if rotary:
        if a.mrope and inp.mrope_positions is not None:
            q = rope.apply_mrope(q, inp.mrope_positions, a.rope_theta, a.mrope_sections)
            k = rope.apply_mrope(k, inp.mrope_positions, a.rope_theta, a.mrope_sections)
        else:
            q = _rotate(a, q, inp.positions, is_global)
            k = _rotate(a, k, inp.positions, is_global)
    return q, k, v


def _rotate(a: AttentionConfig, x, positions, is_global):
    """The rotary of the layer's kind; under the scan's traced flag, both
    kinds' rotations and a select (exact) where the kinds differ."""
    glob, loc = a.rotary(True), a.rotary(False)
    if glob == loc:
        return rope.apply_rope(x, positions, *glob)
    if isinstance(is_global, bool):
        return rope.apply_rope(x, positions, *(glob if is_global else loc))
    return jnp.where(is_global, rope.apply_rope(x, positions, *glob),
                     rope.apply_rope(x, positions, *loc))


def prefill_attention(
    p: dict,
    a: AttentionConfig,
    h: jnp.ndarray,
    inp: AttnInputs,
    *,
    is_global: jnp.ndarray | bool = True,
    lora: Optional[dict] = None,
    lora_scale: float = 1.0,
    causal: bool = True,
    rotary: bool = True,
    kv_mask: Optional[jnp.ndarray] = None,  # (B, S) valid-key mask
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full-sequence attention.  Returns (out, q, k, v).

    ``is_global`` may be a traced bool (scanned local/global flag): local
    layers apply the sliding-window mask and the local rotary, global
    layers neither.  Both cases share one kernel call by selecting the
    window value (huge = unbounded).  ``kv_mask`` excludes keys
    (bucket-padded prompt rows) from every query.
    """
    q, k, v = qkv(p, a, h, inp, lora=lora, lora_scale=lora_scale,
                  rotary=rotary, is_global=is_global)
    window = layer_window(a, is_global) if causal else None
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              kv_mask=kv_mask)
    B, S = h.shape[:2]
    out = out.reshape(B, S, a.q_dim)
    out = linear(out, p["wo"], lora=_lora_for(lora, "wo"),
                 lora_mask=inp.lookahead_mask, lora_scale=lora_scale)
    return out, q, k, v


def chunk_prefill_attention(
    p: dict,
    a: AttentionConfig,
    h: jnp.ndarray,  # (B, C, D) chunk hidden states
    inp: AttnInputs,  # positions = q_offset + arange(C)
    k_buf: jnp.ndarray,  # (B, K, KV, hd) materialized prompt keys so far
    v_buf: jnp.ndarray,
    *,
    q_offset,  # scalar int32 (traced) — absolute position of chunk row 0
    is_global: jnp.ndarray | bool = True,
    score_masses: bool = False,  # fused eviction-score partials (h2o)
    n_total=None,  # scalar int32 — true prompt length (masks pad rows)
    lora: Optional[dict] = None,
    lora_scale: float = 1.0,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
           Optional[jnp.ndarray]]:
    """Streaming-prefill attention: project + rotate the chunk, append its
    K/V into the prompt buffer at ``q_offset``, and attend the chunk's
    queries over prior-chunk keys plus causal self-attention within the
    chunk (``ops.chunk_attention``).  Returns (out, q, k_buf', v_buf',
    masses) — the rotary-encoded q feeds the streaming eviction scores, the
    updated buffers carry the materialized KV to the next chunk, and
    ``masses`` is the fused per-key column-mass partial (B, H, K) when
    ``score_masses`` is set (None otherwise): the cumulative (h2o) policy's
    chunk contribution, emitted by the attention kernel itself with rows at
    or past ``n_total`` masked to zero.

    The buffer must be deep enough for the write (``q_offset + C <= K``);
    ``jax.lax.dynamic_update_slice`` would otherwise silently clamp the
    start index and corrupt earlier chunks' keys.
    """
    q, k, v = qkv(p, a, h, inp, lora=lora, lora_scale=lora_scale,
                  is_global=is_global)
    with jax.named_scope("kv_buffer_write"):
        k_buf = jax.lax.dynamic_update_slice(
            k_buf, k.astype(k_buf.dtype), (0, q_offset, 0, 0))
        v_buf = jax.lax.dynamic_update_slice(
            v_buf, v.astype(v_buf.dtype), (0, q_offset, 0, 0))
    smesh = model_shard_mesh(inp.mesh, a)

    def attend(window):  # -> (out, masses or None)
        if smesh is not None:
            return _sharded_chunk_attention(
                q, k_buf, v_buf, q_offset=q_offset, window=window,
                score_masses=score_masses, n_total=n_total, mesh=smesh)
        if score_masses:
            return ops.chunk_attention(
                q, k_buf, v_buf, q_offset=q_offset, window=window,
                score_masses=True, n_total=n_total)
        return ops.chunk_attention(q, k_buf, v_buf, q_offset=q_offset,
                                   window=window), None

    out, masses = by_kind(a, is_global, attend)
    B, C = h.shape[:2]
    out = out.reshape(B, C, a.q_dim)
    out = sharded_wo_linear(out, p["wo"], smesh, lora=_lora_for(lora, "wo"),
                            lm=inp.lookahead_mask, ls=lora_scale)
    return out, q, k_buf, v_buf, masses


_HUGE_WINDOW = 1 << 30


def _patterned(a: AttentionConfig) -> bool:
    return a.global_every > 0 or len(a.global_layers) > 0


def layer_window(a: AttentionConfig, is_global) -> "int | jnp.ndarray | None":
    """Resolve the attention window for one layer.

    Returns None (full attention), a static int (uniform sliding window) or a
    traced int32 scalar (scanned local/global pattern: global layers get a
    window larger than any sequence, which the masks treat as unbounded).
    """
    if _patterned(a):
        if isinstance(is_global, bool):
            return None if is_global else a.sliding_window
        return jnp.where(
            jnp.asarray(is_global),
            jnp.int32(_HUGE_WINDOW),
            jnp.int32(a.sliding_window),
        )
    if a.sliding_window > 0:
        return a.sliding_window
    return None


def by_kind(a: AttentionConfig, is_global, fn):
    """``fn(window)`` with the layer's window as a static value, which the
    Pallas kernels need.  Under the layer scan of a local:global pattern
    the layer's kind is a traced flag: a ``lax.cond`` then picks between
    the full layers' call (``window=None``) and the windowed layers'
    (``window=sliding_window``), so both kinds run the kernels.  The
    cond wraps only the kernel call: the layer's weights stay in the
    scan, where their slices fuse into the matmuls."""
    if isinstance(is_global, bool) or not _patterned(a):
        return fn(layer_window(a, is_global))
    return jax.lax.cond(is_global, lambda: fn(None),
                        lambda: fn(a.sliding_window))


# -- tensor-parallel kernel dispatch ----------------------------------------
#
# With a ("data", "model") serving mesh, attention runs per model shard
# over its local head slice: contiguous kv-head shards own exactly their q
# heads' GQA groups (H = G·KV keeps group boundaries shard-aligned), and
# every per-head reduction sweeps the full sequence in the *same order* as
# the unsharded call — so per-head outputs, the fused score-mass partials,
# and the eviction kept-sets derived from them are bit-identical to
# single-device serving, with no collective inside the attention block
# (shards combine downstream: ``sharded_wo_linear`` all-gathers the head
# slices and contracts them against ``wo``'s local output columns, the
# split ``param_specs`` stores it in).  Pallas
# kernels have no GSPMD partition rule, so the forced-Pallas dispatch
# *requires* these shard_map wrappers to stay on the kernel path.


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def pin_activations(h, mesh):
    """Pin a scan-carried activation to batch-only sharding (feature dim
    unsharded).  Left to itself, GSPMD may feature-shard the carry between
    layers to match the column-split outputs of the ``wo``/``w_down``
    matmuls — turning every ``rms_norm`` mean into a psum of per-shard
    partials whose different summation order perturbs activations by bf16
    ulps, and with them the eviction scores sharded serving promises to
    keep bit-exact."""
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return h
    if int(mesh.shape["model"]) == 1:
        return h
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    bspec = _batch_spec(mesh, h.shape[0])
    return jax.lax.with_sharding_constraint(
        h, NamedSharding(mesh, P(bspec, *(None,) * (h.ndim - 1))))


def pin_heads(x, smesh):
    """Pin a (B, S, heads, hd) projection to head-sharded on "model" — the
    canonical Megatron column split.  Unpinned, GSPMD is free to realize
    the projection as a contraction-split dot (psum of per-shard partial
    sums over d_model), whose different summation association perturbs the
    result by bf16 ulps vs the single-device program."""
    if smesh is None:
        return x
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    return jax.lax.with_sharding_constraint(
        x, NamedSharding(smesh, P(None, None, "model", None)))


def _sharded_qkv_project(p, h, lm, lora, lora_scale, smesh):
    """q/k/v projections column-parallel under shard_map.

    GSPMD is free to realize ``h @ w`` as a contraction-split dot (psum of
    per-shard partials over d_model) whose summation association differs
    from the single-device dot by bf16 ulps — and
    ``with_sharding_constraint`` pins layouts, not dot algorithms, so it
    cannot forbid that choice (observed: the observation pass's
    LoRA-bearing k projection drifts inside ``lax.scan`` even with its
    inputs and outputs pinned).  Under shard_map each shard computes its
    local head columns with the full d_model contraction in the
    single-device order, making the projection bit-exact by construction.
    The LoRA delta (observation rows) rides along: ``xm @ A`` is computed
    in full on every shard (A is replicated and rank-tiny) and ``@ B``
    takes the local column slice.
    """
    from jax.sharding import PartitionSpec as P

    names = ("wq", "wk", "wv")
    ws = {n: p[n] for n in names}
    bs = {n: p["b" + n[1:]] for n in names
          if p.get("b" + n[1:]) is not None}
    lo = {n: lora[n] for n in names
          if lora is not None and lora.get(n) is not None}
    w_specs = {n: column_split() for n in ws}
    b_specs = {n: P("model") for n in bs}
    lo_specs = {n: {"a": P(None, None), "b": column_split()} for n in lo}
    bspec = _batch_spec(smesh, h.shape[0])
    have_lm = lm is not None

    def local(hh, wsl, bsl, losl, *rest):
        lml = rest[0] if have_lm else None
        outs = tuple(
            linear(hh, wsl[n], bsl.get(n), lora=losl.get(n),
                   lora_mask=lml, lora_scale=lora_scale)
            for n in names)
        return outs

    arrs = [h, ws, bs, lo]
    specs = [P(bspec, None, None), w_specs, b_specs, lo_specs]
    if have_lm:
        arrs.append(lm)
        specs.append(P(bspec, None, None))
    cspec = P(bspec, None, "model")
    return _shard_map(local, smesh, tuple(specs), (cspec,) * 3)(*arrs)


def replicated_apply(fn, smesh, *args):
    """Run ``fn(*args)`` identically on every shard under shard_map.

    The escape hatch for small computations that must be bit-exact vs the
    single-device program but whose dots GSPMD may re-associate (the
    lookahead-LoRA deltas on the observation rows): inside shard_map there
    is no partitioner, so each shard gathers the operands (declared fully
    replicated) and performs the complete single-device computation in the
    single-device order.  Redundant across shards — reserve it for
    observation-sized work, not the serving hot path.
    """
    if smesh is None:
        return fn(*args)
    from jax.sharding import PartitionSpec as P

    in_specs = tuple(P() for _ in args)
    return _shard_map(lambda *a: fn(*a), smesh, in_specs, P())(*args)


def sharded_wo_linear(out_flat, w, smesh, *, lora=None, lm=None, ls=1.0):
    """Attention out-projection with the contraction in single-device order.

    ``out @ wo`` contracts over the head-sharded dim, and GSPMD's
    realization of that dot is shape-dependent: at some (chunk, length)
    points it psum-splits the contraction, re-associating the bf16 sums
    vs the single-device program.  Here the head-sharded attention output
    is all-gathered *inside* shard_map, then each shard computes the full
    contraction for its local slice of output columns (column-parallel on
    d_model) — no psum ever touches the reduction, so bits match the
    single-device matmul by construction.  The LoRA delta (observation
    rows) follows the same pattern: full ``xm @ A``, column-sliced
    ``@ B``.
    """
    if smesh is None:
        return linear(out_flat, w, lora=lora, lora_mask=lm, lora_scale=ls)
    if w.shape[-1] % int(smesh.shape["model"]):
        return replicated_apply(
            lambda o, wl, lo, lml: linear(o, wl, lora=lo, lora_mask=lml,
                                          lora_scale=ls),
            smesh, out_flat, w, lora, lm)
    from jax.sharding import PartitionSpec as P

    bspec = _batch_spec(smesh, out_flat.shape[0])
    have_lora = lora is not None and lm is not None

    def local(o, wl, *rest):
        of = jax.lax.all_gather(o, "model", axis=2, tiled=True)
        lo = rest[0] if have_lora else None
        lml = rest[1] if have_lora else None
        return linear(of, wl, lora=lo, lora_mask=lml, lora_scale=ls)

    arrs = [out_flat, w]
    specs = [P(bspec, None, "model"), column_split()]
    if have_lora:
        arrs += [lora, lm]
        specs += [{"a": P(None, None), "b": column_split()},
                  P(bspec, None, None)]
    return _shard_map(local, smesh, tuple(specs),
                      P(bspec, None, "model"))(*arrs)


def model_shard_mesh(mesh, a: AttentionConfig):
    """The mesh when per-shard head dispatch applies, else None.

    kv heads must divide the "model" axis (``sharding.manual_tp``);
    anything else degrades to the unsharded call — the same test
    ``param_specs`` makes before it stores weights in the split these
    wrappers read.
    """
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return None
    return mesh if manual_tp(a, int(mesh.shape["model"])) else None


def _batch_spec(mesh, B: int):
    """Shard the batch over the data axes when it divides them."""
    dp = tuple(n for n in mesh.axis_names if n != "model")
    total = 1
    for n in dp:
        total *= int(mesh.shape[n])
    return dp if (dp and B % total == 0) else None


def _sharded_chunk_attention(q, k_buf, v_buf, *, q_offset, window,
                             score_masses, n_total, mesh):
    """``ops.chunk_attention`` per model shard over local head slices."""
    from jax.sharding import PartitionSpec as P

    bspec = _batch_spec(mesh, q.shape[0])
    hspec = P(bspec, None, "model", None)  # heads/kv-heads on axis 2

    arrs = [q, k_buf, v_buf, jnp.asarray(q_offset, jnp.int32)]
    specs = [hspec, hspec, hspec, P()]
    if n_total is not None:
        arrs.append(jnp.asarray(n_total, jnp.int32))
        specs.append(P())

    def local(qv, kv, vv, off, *rest):
        if not score_masses:
            return (ops.chunk_attention(qv, kv, vv, q_offset=off,
                                        window=window),)
        nt = rest[-1] if n_total is not None else None
        return ops.chunk_attention(qv, kv, vv, q_offset=off, window=window,
                                   score_masses=True, n_total=nt)

    out_specs = (hspec, P(bspec, "model", None)) if score_masses else (hspec,)
    res = _shard_map(local, mesh, tuple(specs), out_specs)(*arrs)
    return (res[0], res[1]) if score_masses else (res[0], None)


def sharded_lookahead_score(q_obs, k_buf, n_prompt, *, q_offset, window,
                            row_valid=None, smesh=None):
    """``ops.lookahead_score`` per model shard (observation-pass scoring).

    Scores are per q-head, so each shard scores its local heads over the
    full key sequence — same reduction order as unsharded, no collective.
    ``smesh`` is a mesh already vetted by ``model_shard_mesh`` (None runs
    the plain call).
    """
    if smesh is None:
        return ops.lookahead_score(q_obs, k_buf, n_prompt, q_offset=q_offset,
                                   window=window, row_valid=row_valid)
    from jax.sharding import PartitionSpec as P

    bspec = _batch_spec(smesh, q_obs.shape[0])
    hspec = P(bspec, None, "model", None)
    traced_offset = q_offset is not None and not isinstance(q_offset, int)

    arrs = [q_obs, k_buf]
    specs = [hspec, hspec]
    if traced_offset:
        arrs.append(jnp.asarray(q_offset, jnp.int32))
        specs.append(P())
    if row_valid is not None:
        arrs.append(row_valid)
        specs.append(P(bspec, None))

    def local(qv, kv, *rest):
        i = 0
        off = q_offset
        if traced_offset:
            off = rest[i]
            i += 1
        rv = rest[i] if row_valid is not None else None
        return ops.lookahead_score(qv, kv, n_prompt, q_offset=off,
                                   window=window, row_valid=rv)

    return _shard_map(local, smesh, tuple(specs),
                      P(bspec, "model", None))(*arrs)


def _sharded_paged_decode(q1, k1, v1, pool, table, pb, off, write_ok,
                          new_pos_kv, new_pos, *, window, depth, mesh):
    """Paged append + ``ops.paged_decode_attention`` per model shard.

    The batch stays *replicated* here (no data-axis sharding): the pool
    has no batch dim, so every data rank must apply the full batch's
    scatter to keep its pool replica identical — sharding the batch would
    silently fork the replicas (check_vma=False cannot catch it).
    """
    from jax.sharding import PartitionSpec as P

    kvspec = P(None, "model", None, None)  # pool k/v (N, KV, bs, hd)
    mspec = P(None, "model", None)  # pool pos/mask (N, KV, bs)
    traced_window = window is not None and not isinstance(window, int)

    arrs = [q1, k1, v1, pool["k"], pool["v"], pool["pos"], pool["mask"],
            table, pb, off, write_ok, new_pos_kv, new_pos]
    specs = [P(None, "model", None), P(None, "model", None),
             P(None, "model", None), kvspec, kvspec, mspec, mspec,
             P(None, None), P(None), P(None), P(None),
             P(None, "model"), P(None)]
    if traced_window:
        arrs.append(jnp.asarray(window, jnp.int32))
        specs.append(P())

    def local(qv, kn, vn, pk, pv, ppos, pmask, tab, pbv, offv, wok,
              npkv, np1, *rest):
        win = rest[0] if traced_window else window
        kvl = kn.shape[-2]
        pk = pk.at[pbv, :, offv].set(kn.astype(pk.dtype))
        pv = pv.at[pbv, :, offv].set(vn.astype(pv.dtype))
        ppos = ppos.at[pbv, :, offv].set(npkv)
        pmask = pmask.at[pbv, :, offv].set(
            jnp.broadcast_to(wok[:, None], (wok.shape[0], kvl)))
        out = ops.paged_decode_attention(
            qv, pk, pv, pmask, tab, pos_pool=ppos, new_pos=np1,
            window=win, depth=depth)
        return out, pk, pv, ppos, pmask

    out_specs = (P(None, "model", None), kvspec, kvspec, mspec, mspec)
    return _shard_map(local, mesh, tuple(specs), out_specs)(*arrs)


def decode_attention_step(
    p: dict,
    a: AttentionConfig,
    h1: jnp.ndarray,  # (B, 1, D) current token hidden
    inp: AttnInputs,
    *,
    window=None,
    is_global: jnp.ndarray | bool = True,
) -> tuple[jnp.ndarray, dict]:
    """One decode step against the cache.  Returns (out (B,1,D), new cache).

    Cache layout (leading L axis stripped by the layer scan):
        k/v: (B, C, KV, hd);  pos/mask: (B, C, KV) — *per kv head*, because
        eviction keeps different token positions per head.

    ``inp.cache_cursor`` is either a scalar (lockstep serving: every sequence
    appends at the same slot) or a (B,) vector (continuous batching: slots
    admitted at different times carry independent write cursors; the append
    becomes a per-sequence one-hot scatter).
    """
    cache = inp.cache
    B = h1.shape[0]
    KV = a.num_kv_heads
    q, k_new, v_new = qkv(p, a, h1, inp, is_global=is_global)
    cursor = inp.cache_cursor
    new_pos = jnp.broadcast_to(inp.positions[:, :, None], (B, 1, KV))
    if getattr(cursor, "ndim", 0) == 1:  # per-slot cursors
        C = cache["k"].shape[1]
        sel = jnp.arange(C)[None, :] == jnp.clip(cursor, 0, C - 1)[:, None]
        sel &= (cursor < C)[:, None]  # full caches stop appending
        k = jnp.where(sel[..., None, None], k_new, cache["k"])
        v = jnp.where(sel[..., None, None], v_new, cache["v"])
        pos = jnp.where(sel[..., None], new_pos, cache["pos"])
        mask = cache["mask"] | sel[..., None]
    else:
        k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, cursor, 0, 0))
        v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, cursor, 0, 0))
        pos = jax.lax.dynamic_update_slice(cache["pos"], new_pos,
                                           (0, cursor, 0))
        mask = jax.lax.dynamic_update_slice(
            cache["mask"], jnp.ones((B, 1, KV), bool), (0, cursor, 0)
        )
    att_mask = mask
    if window is not None:
        att_mask = mask & ((new_pos[:, :1] - pos) < window)
    out = ops.decode_attention(q[:, 0], k, v, kv_mask=att_mask)
    out = out.reshape(B, 1, a.q_dim)
    out = sharded_wo_linear(out, p["wo"], model_shard_mesh(inp.mesh, a))
    new_cache = {"k": k, "v": v, "pos": pos, "mask": mask}
    return out, new_cache


def decode_attention_step_paged(
    p: dict,
    a: AttentionConfig,
    h1: jnp.ndarray,  # (B, 1, D) current token hidden
    inp: AttnInputs,
    *,
    table: jnp.ndarray,  # (B, nb) int32 physical block ids (0 = null)
    depth: int,  # static dense-equivalent cache depth (capacity + margin)
    active: Optional[jnp.ndarray] = None,  # (B,) live-slot mask
    window=None,
    is_global: jnp.ndarray | bool = True,
) -> tuple[jnp.ndarray, dict]:
    """One decode step against the *paged* cache (``serving/kv_pool.py``).

    ``inp.cache`` holds this layer's slice of the shared block pool
    ({k, v: (N, KV, bs, hd); pos, mask: (N, KV, bs)}); a slot's logical
    cache row ``c`` of kv head ``h`` lives at
    ``(table[b, c // bs], h, c % bs)``.  The step

    1. appends the new token's K/V at the slot's cursor row — a scatter
       into the slot's own block.  Because the pool is shared, a write
       from a retired / empty slot cannot be reverted with a per-slot
       select the way the dense path does: writes are gated *here* —
       inactive or full slots route their scatter to the null block (id
       0), whose mask stays False (the routed mask value is exactly
       ``False``), so zombie decodes never corrupt a neighbour's blocks;
    2. attends straight out of the pool via ``ops.paged_decode_attention``
       — the Pallas kernel streams K/V/mask/pos tiles through the
       scalar-prefetched block table, so no dense ``(B, depth, ...)``
       copy of the cache ever materializes on the kernel path.  Sliding
       windows ride along: the kernel (and both jnp fallbacks) apply
       ``new_pos - pos < window`` from the pool's ``pos`` metadata, with
       a *traced* window prefetched like the table.

    The bit-exactness contract lives in the jnp dispatch: at serving
    depths ``ops.paged_decode_attention`` falls back to the gather
    oracle (``ref.paged_decode_attention`` with ``depth``), which
    materializes bitwise the rows the dense cache would hold, slices to
    the same static ``depth`` and reduces in the same order as
    ``decode_attention_step`` — so paged serving emits bit-identical
    tokens to dense serving there (tests/test_kv_pool.py proves it per
    policy).  The kernel path is exact-parity within fp tolerance
    (tests/test_paged_decode.py) and is held to a roofline bandwidth
    budget by ``benchmarks/bench_kernels.py``.

    Decode-time eviction rides an optional ``"score"`` leaf in the pool
    slice: when present ((B, depth, KV) cumulative softmax masses), the
    attention call fuses the step's per-row masses
    (``score_masses=True``) and the updated accumulator is returned in
    the new cache dict — the streaming analogue of the dense
    ``decode_attention_step_evicting`` score recurrence, consumed by the
    serving engine's periodic evict-and-compact sweep.  The attention
    output is bitwise unchanged by scoring on every kernel tier.
    """
    pool = inp.cache  # this layer's pool slice
    score = pool.get("score")  # (B, depth, KV) decode-eviction masses
    B = h1.shape[0]
    KV = a.num_kv_heads
    bs = pool["k"].shape[2]
    nb = table.shape[1]
    assert depth <= nb * bs, "block table shallower than the logical cache"
    q, k_new, v_new = qkv(p, a, h1, inp, is_global=is_global)
    cursor = inp.cache_cursor  # (B,) per-slot append cursors
    new_pos = jnp.broadcast_to(inp.positions[:, :, None], (B, 1, KV))

    # -- append (null-routed for inactive / full slots) --
    write_ok = cursor < depth  # full caches stop appending (as dense)
    if active is not None:
        write_ok &= active
    jb = jnp.clip(cursor // bs, 0, nb - 1)
    off = jnp.clip(cursor - jb * bs, 0, bs - 1)
    pb = jnp.take_along_axis(table, jb[:, None], axis=1)[:, 0]
    # a live slot whose append block is missing (table entry 0 — the
    # engine's ensure step should have grown it) must not mark a null-
    # block row valid: that would hand a phantom zero-payload key to
    # every slot whose gaps/tails read that row
    write_ok &= pb != 0
    pb = jnp.where(write_ok, pb, 0)
    smesh = model_shard_mesh(inp.mesh, a)
    if smesh is not None:
        assert score is None, \
            "decode-time eviction scoring is single-device (the engine " \
            "rejects mesh + decode_evict on the paged pool)"
        out, pk, pv, ppos, pmask = _sharded_paged_decode(
            q[:, 0], k_new[:, 0], v_new[:, 0], pool, table, pb, off,
            write_ok, new_pos[:, 0], inp.positions[:, 0],
            window=window, depth=depth, mesh=smesh)
        out = out.reshape(B, 1, a.q_dim)
        out = sharded_wo_linear(out, p["wo"], smesh)
        return out, {"k": pk, "v": pv, "pos": ppos, "mask": pmask}
    pk = pool["k"].at[pb, :, off].set(k_new[:, 0].astype(pool["k"].dtype))
    pv = pool["v"].at[pb, :, off].set(v_new[:, 0].astype(pool["v"].dtype))
    ppos = pool["pos"].at[pb, :, off].set(new_pos[:, 0])
    pmask = pool["mask"].at[pb, :, off].set(
        jnp.broadcast_to(write_ok[:, None], (B, KV)))

    # -- attend in pool layout: the kernel streams tiles through the
    # block table, the jnp gather fallback reproduces the dense step's
    # exact reduction (no dense view is built here on any path) --
    new_cache = {"k": pk, "v": pv, "pos": ppos, "mask": pmask}
    if score is not None:
        from repro.core.scoring import decode_mass_update

        out, masses = ops.paged_decode_attention(
            q[:, 0], pk, pv, pmask, table, pos_pool=ppos,
            new_pos=inp.positions[:, 0], window=window, depth=depth,
            score_masses=True)
        new_cache["score"] = score + decode_mass_update(
            masses, KV, active=write_ok)
    else:
        out = ops.paged_decode_attention(
            q[:, 0], pk, pv, pmask, table, pos_pool=ppos,
            new_pos=inp.positions[:, 0], window=window, depth=depth)
    out = out.reshape(B, 1, a.q_dim)
    out = linear(out, p["wo"])
    return out, new_cache


def cross_attention(
    p: dict,
    a: AttentionConfig,
    h: jnp.ndarray,  # (B, Sq, D) decoder hidden
    enc_k: jnp.ndarray,  # (B, Se, KV, hd) precomputed encoder keys
    enc_v: jnp.ndarray,
    *,
    enc_mask: Optional[jnp.ndarray] = None,
    lora: Optional[dict] = None,
    lora_mask: Optional[jnp.ndarray] = None,
    lora_scale: float = 1.0,
) -> jnp.ndarray:
    """Whisper-style decoder→encoder cross attention (no positions)."""
    B, Sq, _ = h.shape
    q = linear(h, p["wq"], p.get("bq"), lora=_lora_for(lora, "wq"),
               lora_mask=lora_mask, lora_scale=lora_scale)
    q = q.reshape(B, Sq, a.num_heads, a.head_dim)
    out = ops.flash_attention(q, enc_k, enc_v, causal=False, kv_mask=enc_mask)
    out = out.reshape(B, Sq, a.q_dim)
    return linear(out, p["wo"], lora=_lora_for(lora, "wo"),
                  lora_mask=lora_mask, lora_scale=lora_scale)


def cross_attention_decode_evicted(
    p: dict,
    a: AttentionConfig,
    h1: jnp.ndarray,  # (B, 1, D)
    cross_cache: dict,  # k/v (B, Cc, KV, hd), mask (B, Cc, KV)
) -> jnp.ndarray:
    """Single-token cross attention over an *evicted* encoder cache (per-head
    kept sets => per-head masks; beyond-paper cross-KV eviction)."""
    B = h1.shape[0]
    q = linear(h1, p["wq"], p.get("bq")).reshape(B, 1, a.num_heads, a.head_dim)
    out = ops.decode_attention(q[:, 0], cross_cache["k"], cross_cache["v"],
                               kv_mask=cross_cache["mask"])
    return linear(out.reshape(B, 1, a.q_dim), p["wo"])


def encode_kv(p: dict, a: AttentionConfig, h_enc: jnp.ndarray):
    """Project encoder states once into cross-attention K/V."""
    B, Se, _ = h_enc.shape
    k = linear(h_enc, p["wk"], p.get("bk")).reshape(B, Se, a.num_kv_heads, a.head_dim)
    v = linear(h_enc, p["wv"], p.get("bv")).reshape(B, Se, a.num_kv_heads, a.head_dim)
    return k, v


def decode_attention_step_evicting(
    p: dict,
    a: AttentionConfig,
    h1: jnp.ndarray,  # (B, 1, D)
    inp: AttnInputs,
    *,
    window=None,
    is_global: jnp.ndarray | bool = True,
) -> tuple[jnp.ndarray, dict]:
    """Decoding-stage eviction step (beyond-paper: the paper names decode
    eviction as future work).  The cache carries a ``score`` field —
    cumulative attention mass per slot (H2O-style heavy hitters, per kv
    head).  While capacity remains, behave like the plain step; once full,
    the new token overwrites the *lowest-cumulative-score* slot (never the
    newest), so the cache stays within its budget during generation.
    """
    cache = inp.cache
    B = h1.shape[0]
    KV, hd = a.num_kv_heads, a.head_dim
    C = cache["k"].shape[1]
    G = a.num_heads // KV
    q, k_new, v_new = qkv(p, a, h1, inp, is_global=is_global)

    # one-step attention distribution of the new query over current slots,
    # grouped by kv head: (B, KV, G, C)
    qg = q[:, 0].reshape(B, KV, G, hd).astype(jnp.float32)
    logits = jnp.einsum(
        "bkgd,bckd->bkgc", qg, cache["k"].astype(jnp.float32)
    ) / jnp.sqrt(jnp.float32(hd))
    mask_bkc = jnp.moveaxis(cache["mask"], 1, 2)  # (B, KV, C)
    logits = jnp.where(mask_bkc[:, :, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).mean(axis=2)  # (B, KV, C)
    add = jnp.moveaxis(probs, 1, 2)  # (B, C, KV)
    score = cache["score"] + jnp.where(cache["mask"], add, 0.0)

    cursor = inp.cache_cursor
    if getattr(cursor, "ndim", 0) == 1:  # per-slot cursors (continuous batch)
        cursor = cursor[:, None]  # (B, 1) broadcasts against (B, KV)
    full = cursor >= C
    victim = jnp.argmin(jnp.where(cache["mask"], score, jnp.inf), axis=1)
    slot = jnp.where(full, victim, jnp.minimum(cursor, C - 1))  # (B, KV)
    onehot = jax.nn.one_hot(slot, C, axis=1, dtype=jnp.float32)  # (B, C, KV)
    sel = onehot[..., None].astype(cache["k"].dtype)  # (B, C, KV, 1)
    k = cache["k"] * (1 - sel) + k_new * sel  # k_new (B,1,KV,hd) broadcasts
    v = cache["v"] * (1 - sel) + v_new * sel
    new_pos = jnp.broadcast_to(inp.positions[:, :, None], (B, 1, KV))
    pos = jnp.where(onehot > 0, new_pos, cache["pos"])
    mask = cache["mask"] | (onehot > 0)
    score = jnp.where(onehot > 0, add, score)  # fresh slot restarts its tally

    att_mask = mask
    if window is not None:
        att_mask = mask & ((new_pos - pos) < window)
    out = ops.decode_attention(q[:, 0], k, v, kv_mask=att_mask)
    out = linear(out.reshape(B, 1, a.q_dim), p["wo"])
    new_cache = {"k": k, "v": v, "pos": pos, "mask": mask, "score": score}
    return out, new_cache


def _frozen_cache_stats(q, k, v, mask, *, mesh=None):
    """Flash-decode stats over the frozen (possibly sequence-sharded) prompt
    cache.  With a mesh whose "model" axis divides the cache length, the
    computation runs under shard_map: each model rank reduces its local
    sequence shard and the partials merge with pmax/psum — per-layer
    collective traffic drops from gathering the full K/V (33 MB per layer on
    qwen2-vl) to the (B, H[, hd]) stat tensors (§Perf decode iteration)."""
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return ops.decode_attention_stats(q, k, v, kv_mask=mask)
    msize = mesh.shape["model"]
    C = k.shape[1]
    B = q.shape[0]
    if C % msize != 0:
        return ops.decode_attention_stats(q, k, v, kv_mask=mask)
    from jax.sharding import PartitionSpec as P

    dp = tuple(n for n in mesh.axis_names if n != "model")
    dp_total = 1
    for a in dp:
        dp_total *= int(mesh.shape[a])
    bspec = dp if B % dp_total == 0 else None

    def local(qv, kv, vv, mv):
        m, l, acc = ops.decode_attention_stats(qv, kv, vv, kv_mask=mv)
        gm = jax.lax.pmax(m, "model")
        corr = jnp.exp(m - gm)
        gl = jax.lax.psum(l * corr, "model")
        gacc = jax.lax.psum(acc * corr[..., None], "model")
        return gm, gl, gacc

    return _shard_map(
        local, mesh,
        (P(bspec, None, None), P(bspec, "model", None, None),
         P(bspec, "model", None, None), P(bspec, "model", None)),
        (P(bspec, None), P(bspec, None), P(bspec, None, None)),
    )(q, k, v, mask)


def decode_attention_step_split(
    p: dict,
    a: AttentionConfig,
    h1: jnp.ndarray,  # (B, 1, D)
    inp: AttnInputs,
    *,
    window=None,
    is_global: jnp.ndarray | bool = True,
) -> tuple[jnp.ndarray, dict]:
    """Split-cache decode (§Perf decode iteration): the prompt cache is
    *frozen* (read-only — it may stay sequence-sharded on "model" with no
    per-step resharding) and new tokens append into a small *replicated*
    hot ring buffer.  The two segments attend independently and merge via
    online-softmax stats — numerically identical to single-cache attention.

    cache = {k, v, pos, mask (frozen, (B,C,KV,·)),
             hot_k, hot_v, hot_pos, hot_mask ((B,Hb,KV,·))}
    """
    cache = inp.cache
    B = h1.shape[0]
    KV = a.num_kv_heads
    Hb = cache["hot_k"].shape[1]
    q, k_new, v_new = qkv(p, a, h1, inp, is_global=is_global)
    cursor = inp.cache_cursor  # counts hot-buffer appends (ring)
    slot = jnp.mod(cursor, Hb)
    hot_k = jax.lax.dynamic_update_slice(cache["hot_k"], k_new,
                                         (0, slot, 0, 0))
    hot_v = jax.lax.dynamic_update_slice(cache["hot_v"], v_new,
                                         (0, slot, 0, 0))
    new_pos = jnp.broadcast_to(inp.positions[:, :, None], (B, 1, KV))
    hot_pos = jax.lax.dynamic_update_slice(cache["hot_pos"], new_pos,
                                           (0, slot, 0))
    hot_mask = jax.lax.dynamic_update_slice(
        cache["hot_mask"], jnp.ones((B, 1, KV), bool), (0, slot, 0))

    froz_mask = cache["mask"]
    hm = hot_mask
    if window is not None:
        froz_mask = froz_mask & ((new_pos - cache["pos"]) < window)
        hm = hm & ((new_pos - hot_pos) < window)
    s1 = _frozen_cache_stats(q[:, 0], cache["k"], cache["v"], froz_mask,
                             mesh=inp.mesh)
    s2 = ops.decode_attention_stats(q[:, 0], hot_k, hot_v, kv_mask=hm)
    out = ops.merge_attention_stats([s1, s2]).astype(h1.dtype)
    out = linear(out.reshape(B, 1, a.q_dim), p["wo"])
    new_cache = dict(cache)
    new_cache.update({"hot_k": hot_k, "hot_v": hot_v, "hot_pos": hot_pos,
                      "hot_mask": hot_mask})
    return out, new_cache
