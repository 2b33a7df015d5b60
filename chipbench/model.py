"""A configuration file (``chipbench/configs/<config>.json``) turned into
the program's ``ModelConfig``, and its weights made on the device from the
seed.

The weights are the benchmark's own: the program is asked only for the
layout of its parameter tree (``jax.eval_shape`` of its init), and every
value is drawn here, in one jitted call, in the type it is served in.  The
plain reference reads the same arrays, so neither side takes weights the
other made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.common.config import (AttentionConfig, LookaheadConfig,
                                     ModelConfig)

    heads = int(c["num_attention_heads"])
    lk = c["lookahead"]
    return ModelConfig(
        name=c["name"], arch_type="dense",
        num_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]), d_ff=int(c["intermediate_size"]),
        vocab_size=int(c["vocab_size"]),
        attn=AttentionConfig(
            num_heads=heads, num_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["hidden_size"]) // heads, qkv_bias=True,
            rope_theta=float(c["rope_theta"])),
        lookahead=LookaheadConfig(
            n_lookahead=int(lk["n_lookahead"]), lora_rank=int(lk["lora_rank"]),
            lora_alpha=float(lk["lora_alpha"]),
            pool_kernel=int(lk["pool_kernel"]),
            window_size=int(lk["window_size"])),
        norm_eps=float(c["rms_norm_eps"]), act=c["hidden_act"],
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype=c["torch_dtype"], source=c["source"])


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _draw(key, name: str, shape, dtype, *, lora_rank: int = 0):
    """One leaf's values by the role its name gives it."""
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("embed", "emb"):
        x = z * 0.02
    elif name == "a":  # LoRA down-projection (rank r)
        x = z / np.sqrt(lora_rank)
    elif name == "b":  # LoRA up-projection: small, so the update is a nudge
        x = z * 0.002
    elif name.startswith("w") or name == "lm_head":
        x = z / np.sqrt(shape[-2])  # fan-in of a (..., d_in, d_out) matrix
    else:  # biases and RMSNorm scales (the program applies 1 + w)
        x = z * 0.02
    return x.astype(dtype)


def _fill(key, shapes, *, lora_rank: int = 0):
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        _draw(k, _leaf_name(p), s.shape, s.dtype, lora_rank=lora_rank)
        for k, (p, s) in zip(keys, leaves)])


def make_weights(seed: int, cfg, mesh=None):
    """(params, lookahead params) on the device, drawn from ``seed``:
    bf16 model weights laid out as the program's init lays them out (and
    sharded by its ``param_specs`` under a mesh), f32 lookahead modules."""
    from repro.core.lookahead import init_lookahead_params
    from repro.models import transformer as tf

    key = jax.random.fold_in(jax.random.PRNGKey(0), seed % (1 << 31))
    key = jax.random.fold_in(key, seed >> 31)
    kp, kl = jax.random.split(key)
    shapes = jax.eval_shape(lambda k: tf.init_params(k, cfg), kp)
    lshapes = jax.eval_shape(
        lambda k, layers: init_lookahead_params(k, cfg, layers),
        kl, shapes["layers"])
    out_sh = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.common.sharding import param_specs
        out_sh = (jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                               param_specs(cfg, mesh),
                               is_leaf=lambda x: isinstance(x, P)),
                  jax.tree.map(lambda _: NamedSharding(mesh, P()), lshapes))
    r = cfg.lookahead.lora_rank
    make = jax.jit(lambda kp, kl: (_fill(kp, shapes),
                                   _fill(kl, lshapes, lora_rank=r)),
                   out_shardings=out_sh)
    return make(kp, kl)
