"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
block shapes whose minor dims are not (8, 128)-tileable, 1-D scratch,
fast-memory overruns.  These tests lower and compile each serving-path
kernel at qwen2-1.5b widths (H=12, KV=2, head_dim 128, bf16) — and the
windowed paged variant at gemma3-1b widths (head_dim 256, KV=1) — for a
``v5e:2x2`` topology that the TPU compiler describes without a chip
attached.  Nothing runs; a compile that passes is not a chip run.

The topology is described inside a module fixture (never at import), so
pytest-xdist workers all collect the same tests and only the worker given
this file loads the TPU compiler.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.chunk_attention import (chunk_attention_masses_pallas,
                                           chunk_attention_pallas)
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lookahead_score import lookahead_score_pallas
from repro.kernels.paged_attention import (paged_decode_attention_pallas,
                                           paged_decode_masses_pallas)

# qwen2-1.5b attention widths
H, KV, HD = 12, 2, 128
BF16 = jnp.bfloat16
# pool block size chip_smoke.py serves with, and the launcher's default
POOL_BLOCK_SIZES = (64, 16)


@pytest.fixture(scope="module")
def topology():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology executable can be written to the persistent
    # cache but never read back without a chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topology):
    return SingleDeviceSharding(topology.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("fused", [False, True])
def test_chunk_attention_compiles(one_chip, fused):
    C, K = 256, 4096

    def f(q, k, v, off, nt):
        if fused:
            return chunk_attention_masses_pallas(q, k, v, off, nt,
                                                 block_k=1024)
        return chunk_attention_pallas(q, k, v, off, block_k=1024)

    _compile(f, one_chip, ((1, C, H, HD), BF16), ((1, K, KV, HD), BF16),
             ((1, K, KV, HD), BF16), ((), jnp.int32), ((), jnp.int32))


def test_lookahead_score_compiles(one_chip):
    n_obs, n_prompt = 32, 4000

    def f(q, k, off, rv):
        return lookahead_score_pallas(q, k, n_prompt, q_offset=off,
                                      row_valid=rv, block_k=2048)

    _compile(f, one_chip, ((1, n_obs, H, HD), BF16),
             ((1, n_prompt + n_obs, KV, HD), BF16), ((), jnp.int32),
             ((1, n_obs), jnp.bool_))


@pytest.mark.parametrize("bs", POOL_BLOCK_SIZES)
@pytest.mark.parametrize("fused", [False, True])
def test_paged_decode_compiles(one_chip, bs, fused):
    B, N, nb = 4, 1024, 4608 // bs
    kern = paged_decode_masses_pallas if fused \
        else paged_decode_attention_pallas

    _compile(kern, one_chip, ((B, H, HD), BF16), ((N, KV, bs, HD), BF16),
             ((N, KV, bs, HD), BF16), ((N, KV, bs), jnp.bool_),
             ((B, nb), jnp.int32))


@pytest.mark.parametrize("fused", [False, True])
def test_paged_decode_windowed_compiles(one_chip, fused):
    # gemma3-1b widths: 4 q heads over one kv head of 256, window 512
    B, N, bs, nb, h, hd = 4, 512, 64, 32, 4, 256
    kern = paged_decode_masses_pallas if fused \
        else paged_decode_attention_pallas

    def f(q, kp, vp, mp, pp, tbl, npos):
        return kern(q, kp, vp, mp, tbl, pos_pool=pp, new_pos=npos,
                    window=512)

    _compile(f, one_chip, ((B, h, hd), BF16), ((N, 1, bs, hd), BF16),
             ((N, 1, bs, hd), BF16), ((N, 1, bs), jnp.bool_),
             ((N, 1, bs), jnp.int32), ((B, nb), jnp.int32),
             ((B,), jnp.int32))


def test_flash_and_dense_decode_compile(one_chip):
    S = 1024
    _compile(lambda q, k, v: flash_attention_pallas(q, k, v), one_chip,
             ((1, S, H, HD), BF16), ((1, S, KV, HD), BF16),
             ((1, S, KV, HD), BF16))
    _compile(lambda q, k, v, m: decode_attention_pallas(q, k, v, kv_mask=m),
             one_chip, ((4, H, HD), BF16), ((4, 3000, KV, HD), BF16),
             ((4, 3000, KV, HD), BF16), ((4, 3000), jnp.bool_))


def _named_cases():
    """name -> (function, argument shapes) of each Pallas kernel, small."""
    C, K, n_obs, n_prompt = 256, 1024, 32, 1000
    B, N, bs, nb = 2, 64, 64, 4
    chunk = ((1, C, H, HD), BF16), ((1, K, KV, HD), BF16), \
        ((1, K, KV, HD), BF16), ((), jnp.int32), ((), jnp.int32)
    pool = ((B, H, HD), BF16), ((N, KV, bs, HD), BF16), \
        ((N, KV, bs, HD), BF16), ((N, KV, bs), jnp.bool_), \
        ((B, nb), jnp.int32)
    return {
        "chunk_attention": (
            lambda q, k, v, off, nt: chunk_attention_pallas(
                q, k, v, off, block_k=512), chunk),
        "chunk_attention_window": (
            lambda q, k, v, off, nt: chunk_attention_pallas(
                q, k, v, off, window=512, block_k=512), chunk),
        "chunk_attention_masses": (
            lambda q, k, v, off, nt: chunk_attention_masses_pallas(
                q, k, v, off, nt, block_k=512), chunk),
        "lookahead_score": (
            lambda q, k, off, rv: lookahead_score_pallas(
                q, k, n_prompt, q_offset=off, row_valid=rv, block_k=512),
            (((1, n_obs, H, HD), BF16), ((1, n_prompt + n_obs, KV, HD), BF16),
             ((), jnp.int32), ((1, n_obs), jnp.bool_))),
        "flash_attention": (
            lambda q, k, v: flash_attention_pallas(q, k, v),
            (((1, 512, H, HD), BF16), ((1, 512, KV, HD), BF16),
             ((1, 512, KV, HD), BF16))),
        "decode_attention": (
            lambda q, k, v, m: decode_attention_pallas(q, k, v, kv_mask=m),
            (((B, H, HD), BF16), ((B, K, KV, HD), BF16),
             ((B, K, KV, HD), BF16), ((B, K), jnp.bool_))),
        "paged_attention": (paged_decode_attention_pallas, pool),
        "paged_attention_masses": (paged_decode_masses_pallas, pool),
    }


@pytest.mark.parametrize("name", list(_named_cases()))
def test_kernel_carries_its_name(one_chip, name):
    """Each kernel's custom call is named after the kernel, the name by
    which a device trace's op events tell the kernels apart."""
    fn, shapes = _named_cases()[name]
    text = _compile(fn, one_chip, *shapes).as_text()
    calls = re.findall(r"%([\w.-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                       text)
    assert calls and all(re.sub(r"\.\d+$", "", c) == name for c in calls), \
        calls


def test_kv_copies_carry_their_scope(one_chip):
    """The head-major re-layout before a kernel runs under the
    ``kv_head_major`` scope, which the ops' metadata keeps."""
    fn, shapes = _named_cases()["chunk_attention"]
    text = _compile(fn, one_chip, *shapes).as_text()
    assert re.search(r'op_name="[^"]*/kv_head_major/', text)


def test_kernel_keeps_its_name_under_shard_map(topology, monkeypatch):
    """Scored as the four-chip cell scores, per model shard under
    ``shard_map`` over a described v5e:2x2 model=4 mesh (7B widths: 28 q
    heads, 4 kv heads), the kernel's custom call still carries the
    kernel's name."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ops
    from repro.models.attention import sharded_lookahead_score

    # this process's backend is the CPU: take the chip's kernel path
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_pallas_interpret", lambda: False)
    mesh = Mesh(np.asarray(topology.devices).reshape(1, 4),
                ("data", "model"))
    n_obs, n_prompt, h, kv = 32, 1000, 28, 4

    def f(q, k, off):
        return sharded_lookahead_score(q, k, n_prompt, q_offset=off,
                                       window=None, smesh=mesh)

    sh = NamedSharding(mesh, P())
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in (
        ((1, n_obs, h, HD), BF16), ((1, n_prompt + n_obs, kv, HD), BF16),
        ((), jnp.int32))]
    text = jax.jit(f).lower(*args).compile().as_text()
    calls = re.findall(r"%([\w.-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                       text)
    assert calls and all(re.sub(r"\.\d+$", "", c) == "lookahead_score"
                         for c in calls), calls


def _tp_programs(mesh):
    """The serving engine's three sharded programs (a prefill chunk, the
    finalize observation pass, a paged decode chunk) for a small model
    whose q heads, kv heads, d_model and d_ff all divide model=4, with
    arguments laid out as the engine lays them out: weights by
    ``param_specs``, the pool by ``pool_specs``, the rest replicated."""
    import dataclasses

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.common import sharding as sh
    from repro.common.config import EvictionConfig
    from repro.configs import get_smoke_config
    from repro.core import policies
    from repro.core.lookahead import init_lookahead_params
    from repro.models import transformer as tf

    base = get_smoke_config("smollm-135m")
    cfg = dataclasses.replace(
        base, d_model=512, d_ff=1536,
        attn=dataclasses.replace(base.attn, num_heads=8, num_kv_heads=4,
                                 head_dim=HD))
    chunk, cap, bs, slots, depth = 128, 512, 64, 4, 256

    def init():
        return tf.init_params(jax.random.PRNGKey(0), cfg)

    def replicated(shapes):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, P())),
            shapes)

    pshapes = jax.eval_shape(init)
    params = sh.with_sharding(pshapes, sh.param_specs(cfg, mesh), mesh)
    lkv = replicated(jax.eval_shape(lambda: init_lookahead_params(
        jax.random.PRNGKey(1), cfg, init()["layers"])))
    state = replicated(jax.eval_shape(
        lambda: tf.init_chunk_state(cfg, "lookaheadkv", 1, cap)))
    L, kv = cfg.num_layers, cfg.attn.num_kv_heads
    pspec = sh.pool_specs(cfg, mesh)
    pool = {n: jax.ShapeDtypeStruct(
        (L, 32, kv, bs) + ((HD,) if n in ("k", "v") else ()),
        {"k": BF16, "v": BF16, "pos": jnp.int32, "mask": jnp.bool_}[n],
        sharding=NamedSharding(mesh, pspec[n])) for n in pspec}

    def rep(shape, dtype=jnp.int32):
        return replicated(jax.ShapeDtypeStruct(shape, dtype))

    def chunk_fn(params, state, tokens, n_total):
        return tf.prefill_chunk(params, cfg, state, tokens, n_total,
                                policy="lookaheadkv", mesh=mesh)

    def finalize_fn(params, lkv, state, n_total, seeds):
        return tf.prefill_finalize(
            params, cfg, state, n_total, policy="lookaheadkv",
            evict=EvictionConfig(budget=128), lkv_params=lkv,
            extra_slots=16, seeds=seeds, mesh=mesh)

    def decode_fn(params, tok, table, cursor, next_pos, pool, active):
        cache = {"attn": {"table": table}, "pool": pool, "cursor": cursor,
                 "next_pos": next_pos}
        last, cache, toks = policies.decode_chunk(
            params, cfg, tok, cache, 2, active=active, paged_depth=depth,
            mesh=mesh)
        return last, cache["pool"], toks

    programs = {
        "chunk": (chunk_fn, (params, state, rep((1, chunk)), rep(()))),
        "finalize": (finalize_fn,
                     (params, lkv, state, rep(()), rep((1,)))),
        "decode": (decode_fn,
                   (params, rep((slots, 1)), rep((slots, depth // bs)),
                    rep((slots,)), rep((slots, 1)), pool,
                    rep((slots,), jnp.bool_))),
    }
    return cfg, pshapes, programs


@pytest.mark.parametrize("program", ["chunk", "finalize", "decode"])
def test_model4_programs_move_no_weights(topology, monkeypatch, program):
    """Under model=4 on a described v5e:2x2, the sharded serving programs
    read ``wo`` and ``w_down`` in the split ``param_specs`` stores them
    in: the optimized HLO has no all-to-all (a stored/read mismatch
    re-lays both weights out between the chips in every program), and
    each stacked leaf's spec is the ``in_specs`` its ``shard_map``
    declares for one layer's slice."""
    import numpy as np
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from repro.common import sharding as sh
    from repro.kernels import ops
    from repro.models import attention as attn_mod

    # this process's backend is the CPU: take the chip's kernel path
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_pallas_interpret", lambda: False)
    declared: dict = {}
    shard_map = attn_mod._shard_map

    def recording(f, mesh, in_specs, out_specs):
        mapped = shard_map(f, mesh, in_specs, out_specs)

        def call(*args):
            specs = jax.tree.leaves(in_specs,
                                    is_leaf=lambda x: isinstance(x, P))
            for a, spec in zip(jax.tree.leaves(args), specs, strict=True):
                declared.setdefault(a.shape, set()).add(tuple(spec))
            return mapped(*args)
        return call

    monkeypatch.setattr(attn_mod, "_shard_map", recording)
    mesh = Mesh(np.asarray(topology.devices).reshape(1, 4),
                ("data", "model"))
    cfg, pshapes, programs = _tp_programs(mesh)
    fn, args = programs[program]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"= \S+ all-to-all(-start)?\(", text)
    stored = sh.param_specs(cfg, mesh)["layers"]
    for block, name in (("attn", "wo"), ("mlp", "w_down")):
        leaf = pshapes["layers"][block][name]
        spec = stored[block][name]
        assert declared.get(leaf.shape[1:]) == {tuple(spec)[1:]}, (
            name, spec, declared.get(leaf.shape[1:]))


def test_mellum2_period_runs_both_chunk_kernels(one_chip, monkeypatch):
    """One period of Mellum2 (three sliding layers, one full) at published
    widths, 8 of 64 experts held: the chunk program lowers every layer's
    attention to the Pallas kernel, the windowed layers' under their own
    name."""
    import dataclasses

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import transformer as tf

    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_pallas_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("mellum2-12b-ep8"), num_layers=4)

    def sds(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = sds(jax.eval_shape(lambda k: tf.init_params(k, cfg),
                                jax.random.PRNGKey(0)))
    state = sds(jax.eval_shape(
        lambda: tf.init_chunk_state(cfg, "lookaheadkv", 1, 2048)))
    i32 = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
           for s in ((1, 256), (), (cfg.moe.held,))]
    text = jax.jit(lambda p, s, t, n, r: tf.prefill_chunk(
        p, cfg, s, t, n, policy="lookaheadkv", routed=r)).lower(
            params, state, *i32).compile().as_text()
    calls = re.findall(
        r"%([\w.-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    assert {re.sub(r"\.\d+$", "", c) for c in calls} == {
        "chunk_attention", "chunk_attention_window"}, calls
