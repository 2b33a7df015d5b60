"""RoPE / M-RoPE properties + sharding-spec validity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import sweep_cases
from repro.configs import ARCH_IDS
from repro.models import rope


def _case(rng):
    return dict(B=int(rng.integers(1, 3)), S=int(rng.integers(4, 40)),
                H=int(rng.integers(1, 4)),
                hd=int(rng.choice([16, 32, 64])),
                seed=int(rng.integers(1 << 30)))


@pytest.mark.parametrize("case", sweep_cases(31, 6, _case))
def test_rope_preserves_norm_and_relativity(case):
    key = jax.random.PRNGKey(case["seed"])
    B, S, H, hd = case["B"], case["S"], case["H"], case["hd"]
    x = jax.random.normal(key, (B, S, H, hd))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    r = rope.apply_rope(x, pos, 10_000.0)
    # rotation preserves norms
    np.testing.assert_allclose(
        jnp.linalg.norm(r, axis=-1), jnp.linalg.norm(x, axis=-1),
        atol=1e-4, rtol=1e-4)
    # relativity: <q_i, k_j> depends only on i - j
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, hd))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, hd))
    def dot_at(pi, pj):
        qi = rope.apply_rope(q, jnp.full((1, 1), pi), 1e4)
        kj = rope.apply_rope(k, jnp.full((1, 1), pj), 1e4)
        return float(jnp.sum(qi * kj))
    assert abs(dot_at(5, 3) - dot_at(10, 8)) < 1e-4
    assert abs(dot_at(7, 0) - dot_at(17, 10)) < 1e-4


def test_mrope_text_equals_rope():
    """With t == h == w == position, M-RoPE must reduce to plain RoPE."""
    key = jax.random.PRNGKey(0)
    B, S, H, hd = 2, 12, 2, 32
    x = jax.random.normal(key, (B, S, H, hd))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    plain = rope.apply_rope(x, pos, 1e4)
    sections = (4, 6, 6)
    m = rope.apply_mrope(x, rope.text_mrope_positions(pos), 1e4, sections)
    np.testing.assert_allclose(plain, m, atol=1e-5, rtol=1e-5)


def test_mrope_streams_differ():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1, 4, 1, 32))
    pos = jnp.arange(4)[None]
    mp = rope.text_mrope_positions(pos)
    mp2 = mp.at[1].add(7)  # shift the height stream
    a = rope.apply_mrope(x, mp, 1e4, (4, 6, 6))
    b = rope.apply_mrope(x, mp2, 1e4, (4, 6, 6))
    assert not np.allclose(a, b)


# ---------------------------------------------------------------------------
# sharding specs
# ---------------------------------------------------------------------------


def test_param_specs_structure_and_divisibility():
    """Every spec matches its leaf's rank, and any sharded dim divides the
    production-mesh axis size — for all ten architectures."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.common import sharding as sh
    from repro.configs import ARCH_IDS, get_config
    from repro.models import transformer as tf

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    sizes = {"data": 16, "model": 16, "pod": 2}
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        shapes = jax.eval_shape(
            lambda cfg=cfg: tf.init_params(jax.random.PRNGKey(0), cfg))
        specs = sh.param_specs(cfg, FakeMesh())
        for (path, leaf), (_, spec) in zip(
                jax.tree_util.tree_flatten_with_path(shapes)[0],
                jax.tree_util.tree_flatten_with_path(
                    specs, is_leaf=lambda x: isinstance(x, P))[0]):
            assert isinstance(spec, P), (arch, path)
            assert len(spec) <= leaf.ndim, (arch, path, spec, leaf.shape)
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                total = int(np.prod([sizes[a] for a in axes]))
                assert leaf.shape[dim] % total == 0, (
                    arch, path, spec, leaf.shape)


def test_cache_specs_cover_cache_tree():
    from repro.common import sharding as sh
    from repro.configs import get_config
    from repro.models import transformer as tf

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    for arch in ("qwen2-1.5b", "hymba-1.5b", "whisper-small", "mamba2-130m"):
        cfg = get_config(arch)
        cap = 32768 if cfg.uses_attention else 0
        cache = jax.eval_shape(
            lambda cfg=cfg, cap=cap: tf.init_decode_cache(cfg, 128, cap,
                                                          fill_len=cap - 1
                                                          if cap else 0))
        specs = sh.cache_specs(cfg, FakeMesh(), 128, cap)
        jax.tree.map(lambda s, sp: None, cache, specs)  # structure matches


def _arch_specs(arch, msize):
    from jax.sharding import PartitionSpec as P

    from repro.common import sharding as sh
    from repro.configs import get_config
    from repro.models import transformer as tf

    class FakeMesh:
        shape = {"data": 256 // msize, "model": msize}

    cfg = get_config(arch)
    shapes = jax.eval_shape(
        lambda: tf.init_params(jax.random.PRNGKey(0), cfg))
    specs = sh.param_specs(cfg, FakeMesh())
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    spec_leaves = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    out = {}
    for (path, leaf), (_, spec) in zip(leaves, spec_leaves):
        key = "/".join(str(getattr(p, "key", p)) for p in path)
        out[key] = (leaf, spec)
    return cfg, FakeMesh.shape, out


@pytest.mark.parametrize("msize", [16, 4])
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_wo_w_down_stored_as_manual_tp_reads_them(arch, msize):
    """``wo`` and the dense ``w_down`` of the decoder stack are stored
    column-split (output columns on "model") exactly where the manual-TP
    ``shard_map`` wrappers run sharded — kv heads divide the axis, and so
    does d_model (and d_ff, for ``w_down``) — and keep the row-parallel
    spec everywhere else (MoE leaves, the whisper encoder and cross
    attention, indivisible meshes).  Every sharded dim still divides."""
    from jax.sharding import PartitionSpec as P

    cfg, sizes, leaves = _arch_specs(arch, msize)
    a = cfg.attn
    tp = (a is not None and a.num_kv_heads % msize == 0
          and a.num_heads % msize == 0)
    fsdp = ("data" if cfg.fsdp and cfg.d_model % sizes["data"] == 0
            else None)

    def on(n):
        return "model" if n % msize == 0 else None

    seen = set()
    for key, (leaf, spec) in leaves.items():
        for dim, ax in enumerate(spec):
            if ax is not None:
                assert leaf.shape[dim] % sizes[ax] == 0, (key, spec)
        parts = key.split("/")
        if parts[-1] not in ("wo", "w_down"):
            continue
        if "moe" in parts:  # expert parallelism: out of this rule's reach
            assert spec[-1] == fsdp and "model" not in spec[2:], (key, spec)
            continue
        block = parts[1] if parts[0] == "layers" else None
        if parts[-1] == "wo":
            cols = block == "attn" and tp and cfg.d_model % msize == 0
            rows_spec = P(None, on(a.num_heads), fsdp)
        else:
            cols = (block == "mlp" and tp and cfg.d_ff % msize == 0
                    and cfg.d_model % msize == 0)
            rows_spec = P(None, on(cfg.d_ff), fsdp)
        if cols:
            rows = fsdp if leaf.shape[1] % sizes["data"] == 0 else None
            assert spec == P(None, rows, "model"), (key, spec)
            seen.add(parts[-1])
        else:
            assert spec == rows_spec, (key, spec)
    # at the four-chip serving mesh both weights of every dense arch whose
    # heads, kv heads, d_model and d_ff divide it are column-split
    if msize == 4 and tp and cfg.moe is None and cfg.d_ff % 4 == 0:
        assert seen == {"wo", "w_down"}, (arch, seen)
