"""Logical→physical sharding rules (MaxText-style, but path-driven).

``param_specs(cfg, mesh)`` mirrors the parameter tree with PartitionSpecs:

* attention q/k/v projections shard their head columns on "model" when
  the head count divides the axis (GQA: k/v shard only when kv heads
  divide, else stay replicated — the standard Megatron GQA compromise);
* the attention ``wo`` and the dense MLP ``w_down`` are stored in the
  split the manual-TP ``shard_map`` forward reads them in (output columns
  on "model", ``column_split``) wherever that forward runs sharded, so no
  weight moves between chips at run time; elsewhere ``wo`` splits its
  head rows and ``w_down`` its d_ff rows, the Megatron row-parallel
  layout the GSPMD forward partitions;
* MLP gate/up shard d_ff columns;
* MoE experts shard the expert dim on "model" (expert parallelism);
* embeddings shard vocab when divisible, else d_model, else replicate;
* ``cfg.fsdp`` additionally shards the d_model dim of big weights over
  "data" (the input rows of a column-split ``wo`` / ``w_down``;
  ZeRO-3-ish storage; XLA all-gathers at use) — beyond-paper;
* Mamba-2 / LoRA / norms / scalars replicate (see DESIGN.md §4 — the SSM
  inner projection is deliberately replicated in the baseline; §Perf
  revisits it).

Every rule degrades to replication when divisibility fails, so every
(arch × mesh) combination lowers.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common.config import ModelConfig
from repro.common.pytree import tree_map_with_path


def _div(n: int, size: int) -> bool:
    return size > 0 and n % size == 0


def column_split(*, stacked: bool = False, rows=None) -> P:
    """The manual-TP split of an ``(in, out)`` weight: output columns on
    "model".  The one definition of that layout — ``param_specs`` stores
    the column-parallel weights in it and the ``shard_map`` wrappers of
    ``models/attention.py`` and ``models/mlp.py`` declare it as their
    ``in_specs``, so stored and read layouts cannot drift apart.
    ``stacked`` adds the leading layer axis; ``rows`` optionally shards
    the input rows too (fsdp storage over "data", gathered at use)."""
    return P(*((None,) if stacked else ()), rows, "model")


def manual_tp(a, msize: int) -> bool:
    """Whether attention runs hand-partitioned per model shard: kv heads
    divide the axis (q heads then divide too, since ``H = G · KV``).
    ``attention.model_shard_mesh`` applies this test to the serving
    mesh."""
    return (a is not None and msize > 1 and _div(a.num_kv_heads, msize)
            and _div(a.num_heads, msize))


def param_specs(cfg: ModelConfig, mesh, *, embed_replicated: bool = False) -> Any:
    """PartitionSpec tree mirroring ``init_params(cfg)`` output.

    ``embed_replicated``: used by the dp_all §Perf variant (batch sharded
    over data *and* model — vocab sharding would then conflict with the
    batch-sharded hidden states at the unembed einsum)."""
    msize = mesh.shape["model"]
    dsize = mesh.shape["data"]
    a = cfg.attn
    fsdp = "data" if (cfg.fsdp and _div(cfg.d_model, dsize)) else None

    shard_q = a is not None and _div(a.num_heads, msize)
    shard_kv = a is not None and _div(a.num_kv_heads, msize)
    shard_ff = _div(cfg.d_ff, msize)
    # where the manual-TP forward runs sharded it reads wo / w_down by
    # output columns: ``attention.sharded_wo_linear`` when d_model divides
    # the axis, ``mlp.apply_sharded`` when d_ff and d_model both do
    tp = manual_tp(a, msize)
    cols_wo = tp and _div(cfg.d_model, msize)
    cols_down = tp and shard_ff and _div(cfg.d_model, msize)
    shard_exp = cfg.moe is not None and _div(cfg.moe.num_experts, msize)
    shard_shared = (cfg.moe is not None and
                    _div(cfg.moe.num_shared_experts * cfg.moe.d_expert, msize))
    # padded vocab always divides the model axis (config.vocab_pad_multiple)
    if embed_replicated:
        embed_spec = P(None, None)
    elif _div(cfg.padded_vocab, msize):
        embed_spec = P("model", None)
    elif _div(cfg.d_model, msize):
        embed_spec = P(None, "model")
    else:
        embed_spec = P(None, None)

    def rule(path: str, leaf) -> P:
        parts = path.split("/")
        name = parts[-1]
        ndim = leaf.ndim
        stacked = "layers" in parts  # leading L axis
        pre = (None,) if stacked else ()

        def tp_read(block: str) -> bool:
            # a decoder-stack block the manual-TP wrappers read; the
            # whisper encoder and cross attention run through GSPMD
            return "encoder" not in parts and parts[-2:-1] == [block]

        def columns(on: bool) -> P:
            if not on:
                return P(*pre, fsdp, None)
            rows = fsdp if _div(leaf.shape[-2], dsize) else None
            return column_split(stacked=stacked, rows=rows)

        if name == "embed":
            return embed_spec
        if name == "lm_head":
            return P(*embed_spec[::-1])
        if name == "pos_emb":
            return P(None, None)
        # --- attention (incl. whisper cross/encoder) ---
        if name == "wq":
            return columns(shard_q)
        if name in ("wk", "wv"):
            return columns(shard_kv)
        if name == "wo":
            if cols_wo and tp_read("attn"):
                return columns(True)
            return P(*pre, "model" if shard_q else None, fsdp)
        if name == "bq":
            return P(*pre, "model" if shard_q else None)
        if name in ("bk", "bv"):
            return P(*pre, "model" if shard_kv else None)
        # --- MoE ---
        if "experts" in parts:
            if name in ("w_gate", "w_up"):
                return P(*pre, "model" if shard_exp else None, fsdp, None)
            if name == "w_down":
                return P(*pre, "model" if shard_exp else None, None, fsdp)
        if name == "router":
            return P(*pre, fsdp, None)
        if "shared" in parts:
            if name in ("w_gate", "w_up"):
                return P(*pre, fsdp, "model" if shard_shared else None)
            if name == "w_down":
                return P(*pre, "model" if shard_shared else None, fsdp)
        # --- dense MLP ---
        if name in ("w_gate", "w_up"):
            return columns(shard_ff)
        if name == "w_down":
            if cols_down and tp_read("mlp"):
                return columns(True)
            return P(*pre, "model" if shard_ff else None, fsdp)
        # --- Mamba-2: replicated in the baseline (DESIGN.md §4) ---
        if name in ("in_proj", "out_proj", "conv_w", "conv_b", "A_log",
                    "D_skip", "dt_bias"):
            return P(*((None,) * ndim))
        # norms, scalars, anything unmatched: replicate
        return P(*((None,) * ndim))

    return tree_map_with_path(lambda p, l: rule(p, l), _as_shaped(cfg))


def _as_shaped(cfg: ModelConfig):
    """Abstract parameter tree (ShapeDtypeStructs) without allocation."""
    from repro.models import transformer as tf

    return jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0), cfg))


def lkv_specs(lkv_shapes: Any) -> Any:
    """Lookahead params replicate everywhere (tiny: <0.5% of model)."""
    return jax.tree.map(lambda x: P(*((None,) * x.ndim)), lkv_shapes)


def batch_axes(mesh) -> tuple:
    return tuple(n for n in mesh.axis_names if n != "model")


def cache_specs(cfg: ModelConfig, mesh, batch: int, capacity: int,
                hot_slots: int = 0) -> Any:
    """Sharding for the decode cache: batch over data axes; kv heads on
    "model" when divisible, else the *sequence* dim on "model" (sequence-
    parallel decode — XLA inserts the softmax partial collectives)."""
    dp = batch_axes(mesh)
    msize = mesh.shape["model"]
    dp_total = int(np.prod([mesh.shape[x] for x in dp]))
    bshard = dp if _div(batch, dp_total) else (
        ("data",) if _div(batch, mesh.shape["data"]) else ())
    bspec = bshard if bshard else None
    a = cfg.attn
    specs: dict = {}
    if a is not None:
        if _div(a.num_kv_heads, msize):
            kv_s, seq_s = "model", None
        elif _div(capacity, msize):
            kv_s, seq_s = None, "model"
        else:
            kv_s = seq_s = None
        if batch == 1 and seq_s is not None:
            # long-context decode: shard the cache sequence over everything
            seq_s = tuple(list(dp) + ["model"])
            bspec = None
        specs["attn"] = {
            "k": P(None, bspec, seq_s, kv_s, None),
            "v": P(None, bspec, seq_s, kv_s, None),
            "pos": P(None, bspec, seq_s, kv_s),
            "mask": P(None, bspec, seq_s, kv_s),
        }
        if hot_slots:
            # split-cache decode: the hot ring replicates over "model" so
            # per-step writes are shard-local (no cache resharding)
            specs["attn"].update({
                "hot_k": P(None, bspec, None, None, None),
                "hot_v": P(None, bspec, None, None, None),
                "hot_pos": P(None, bspec, None, None),
                "hot_mask": P(None, bspec, None, None),
            })
        specs["cursor"] = P()
    if cfg.uses_ssm:
        specs["ssm"] = {
            "conv": P(None, bspec, None, None),
            "state": P(None, bspec, None, None, None),
        }
    if cfg.is_encoder_decoder:
        specs["cross"] = {
            "k": P(None, bspec, None, None, None),
            "v": P(None, bspec, None, None, None),
        }
    specs["next_pos"] = P(bspec, None)
    return specs


def pool_specs(cfg: ModelConfig, mesh) -> Optional[dict]:
    """Sharding for the paged KV block pool (``serving/kv_pool.py``).

    The pool arrays are ``(L, num_blocks, KV, block_size, hd)`` K/V plus
    ``(L, num_blocks, KV, block_size)`` pos/mask; only the kv-head dim is
    sharded, on "model" — blocks are *whole* on every shard, so the host
    free-list allocator and the per-request block tables stay replicated
    and allocation logic is untouched.  Returns None when the mesh has no
    "model" axis or kv heads don't divide it (pool stays single-device /
    replicated)."""
    a = cfg.attn
    if a is None or mesh is None:
        return None
    if "model" not in getattr(mesh, "axis_names", ()):
        return None
    if not _div(a.num_kv_heads, mesh.shape["model"]):
        return None
    return {
        "k": P(None, None, "model", None, None),
        "v": P(None, None, "model", None, None),
        "pos": P(None, None, "model", None),
        "mask": P(None, None, "model", None),
    }


def init_params_sharded(key, cfg: ModelConfig, mesh) -> Any:
    """``transformer.init_params`` created already sharded by
    ``param_specs``: one jitted init whose outputs are laid out per shard,
    so no device ever holds the whole model (a model larger than one
    chip's memory can only be built this way)."""
    from repro.models import transformer as tf

    shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                             param_specs(cfg, mesh),
                             is_leaf=lambda x: isinstance(x, P))
    return jax.jit(lambda k: tf.init_params(k, cfg),
                   out_shardings=shardings)(key)


def mesh_signature(mesh) -> Optional[tuple]:
    """Hashable mesh identity for compile-cache keys: ``(("data", 4),
    ("model", 2))`` — None for no mesh or an all-1 (trivial) mesh, so
    meshless cache keys keep their historical shape."""
    if mesh is None:
        return None
    sig = tuple((n, int(mesh.shape[n])) for n in mesh.axis_names)
    if all(s == 1 for _, s in sig):
        return None
    return sig


def with_sharding(shapes: Any, specs: Any, mesh) -> Any:
    """Attach NamedShardings to a ShapeDtypeStruct tree."""
    return jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, sp)
        ),
        shapes, specs,
    )
