"""A model's weights made on the device from the seed.

The weights are the benchmark's own: the program is asked only for the
layout of its parameter tree (``jax.eval_shape`` of its init, for the
``ModelConfig`` an architecture module builds), and every value is drawn
here, in one jitted call, in the type it is served in.  The plain
reference reads the same arrays, so neither side takes weights the other
made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _draw(key, name: str, shape, dtype, *, lora_rank: int = 0, rule=None):
    """One leaf's values by the role its name gives it, or by the
    architecture's own ``rule(name, z, shape)`` where that gives any."""
    z = jax.random.normal(key, shape, jnp.float32)
    if rule is not None and (x := rule(name, z, shape)) is not None:
        return x.astype(dtype)
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


def _fill(key, shapes, *, lora_rank: int = 0, rule=None):
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        _draw(k, _leaf_name(p), s.shape, s.dtype, lora_rank=lora_rank,
              rule=rule)
        for k, (p, s) in zip(keys, leaves)])


def make_weights(seed: int, cfg, mesh=None, *, arch=None):
    """(params, lookahead params) on the device, drawn from ``seed``:
    bf16 model weights laid out as the program's init lays them out (and
    sharded by its ``param_specs`` under a mesh), f32 lookahead modules.
    The architecture module ``arch`` may give a ``draw(name, z, shape)``
    rule for leaves the roles here do not name (None: the role's draw)."""
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
    rule = getattr(arch, "draw", None)
    make = jax.jit(lambda kp, kl: (_fill(kp, shapes, rule=rule),
                                   _fill(kl, lshapes, lora_rank=r)),
                   out_shardings=out_sh)
    return make(kp, kl)
