"""Each configuration file builds the program's ModelConfig at the
published widths of its source, and the program's parameter tree has the
published parameter count."""

import json
from pathlib import Path

import jax
import pytest

from chipbench import model

CONFIGS = Path(__file__).resolve().parents[1] / "configs"

# from the sources' config.json files (and the Qwen2 report's table)
PUBLISHED = {
    "qwen2-1.5b": dict(num_layers=28, d_model=1536, d_ff=8960, heads=12,
                       kv_heads=2, head_dim=128, vocab=151936, tied=True,
                       params_b=1.54),
    "qwen2-7b-tp4": dict(num_layers=28, d_model=3584, d_ff=18944, heads=28,
                         kv_heads=4, head_dim=128, vocab=152064, tied=False,
                         params_b=7.62),
}


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_published_widths(name):
    c = json.loads((CONFIGS / f"{name}.json").read_text())
    assert c["name"] == name and c["reduced"] == []
    cfg = model.model_config(c)
    want = PUBLISHED[name]
    a = cfg.attn
    assert (cfg.num_layers, cfg.d_model, cfg.d_ff, a.num_heads,
            a.num_kv_heads, a.head_dim, cfg.vocab_size, cfg.tie_embeddings) \
        == (want["num_layers"], want["d_model"], want["d_ff"], want["heads"],
            want["kv_heads"], want["head_dim"], want["vocab"], want["tied"])
    assert a.qkv_bias and a.rope_theta == 1e6 and cfg.norm_eps == 1e-6
    assert cfg.dtype == "bfloat16"
    from repro.models import transformer as tf

    shapes = jax.eval_shape(lambda k: tf.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    # the program pads the vocabulary to a multiple of 256 rows
    pad = (cfg.padded_vocab - cfg.vocab_size) * cfg.d_model \
        * (1 if cfg.tie_embeddings else 2)
    assert abs((n - pad) / 1e9 - want["params_b"]) < 0.01


def test_weights_follow_the_seed():
    c = json.loads((Path(__file__).parent / "data" /
                    "tiny-qwen2.json").read_text())
    cfg = model.model_config(c)
    a, la = model.make_weights(2**31 + 7, cfg)
    b, lb = model.make_weights(2**31 + 7, cfg)
    d, _ = model.make_weights(7, cfg)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), (a, la), (b, lb))
    assert all(jax.tree.leaves(same))
    assert not bool((a["embed"] == d["embed"]).all())
    assert a["embed"].dtype == jax.numpy.bfloat16
    assert la["emb"].dtype == jax.numpy.float32
