"""Architecture modules: Qwen2's reference gives, to the bit, what it gave
before it moved into its module; groups of layers run in order; a
configuration that names no module is refused; and an architecture other
than Qwen2 (top-k experts, sliding and global layers, its own head_dim, no
bias) enters as new files only and is checked like any cell."""

import hashlib
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cells, model, reference, run
from chipbench.tests.test_bench_harness import new_cell_root

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
QWEN2 = cells.arch_module(ROOT, "qwen2")
RECORDED = json.loads((DATA / "tiny-qwen2-reference.json").read_text())


def tiny_qwen2(seed):
    c = json.loads((DATA / RECORDED["config"]).read_text())
    cfg = QWEN2.model_config(c)
    params, lkv = model.make_weights(seed, cfg, arch=QWEN2)
    return c, cfg, params, lkv


def recorded_inputs(seed, n, vocab):
    """The prompt and served tokens the recording drew for ``(seed, n)``."""
    rng = np.random.default_rng([seed, 9])
    for case in RECORDED["cases"]:
        if case["seed"] != seed or case["quant"]:
            continue
        prompt = np.zeros(case["n_pad"], np.int32)
        prompt[:case["n"]] = rng.integers(0, vocab, case["n"])
        gen = rng.integers(0, vocab, RECORDED["served"]).astype(np.int32)
        if case["n"] == n:
            return prompt, gen
    raise KeyError((seed, n))


def sha(x) -> str:
    return hashlib.sha256(np.ascontiguousarray(x).tobytes()).hexdigest()


def qwen2_forward(case, arch=QWEN2):
    c, cfg, params, lkv = tiny_qwen2(case["seed"])
    prompt, gen = recorded_inputs(case["seed"], case["n"], cfg.vocab_size)
    spec = QWEN2.spec_of(cfg, int(c["serving"]["budget"]))
    logits, kept = reference.forward(
        params, lkv, jnp.asarray(prompt), jnp.asarray(case["n"], jnp.int32),
        jnp.asarray(gen), spec=spec, arch=arch, quant=case["quant"])
    return np.asarray(logits), np.asarray(kept)


@pytest.mark.parametrize("case", RECORDED["cases"],
                         ids=[c["key"] for c in RECORDED["cases"]])
def test_qwen2_reference_is_the_recorded_one(case):
    """Logits and kept sets, float32 reference and float8 control, are
    bitwise those recorded before the move."""
    logits, kept = qwen2_forward(case)
    assert int(kept.sum()) == case["kept_count"]
    assert sha(kept) == case["kept_sha256"]
    assert float(logits.astype(np.float64).sum()) == case["logits_sum"]
    assert sha(logits) == case["logits_sha256"]


def test_layer_groups_run_in_order():
    """The same layers given as two groups, each its own scan, give the
    same logits and kept sets as one group."""
    def two_groups(params, lkv, spec):
        (xs,) = QWEN2.layers(params, lkv, spec)
        return [jax.tree.map(lambda a: a[:1], xs),
                jax.tree.map(lambda a: a[1:], xs)]

    split = types.ModuleType("qwen2_in_two_groups")
    split.__dict__.update({k: v for k, v in vars(QWEN2).items()
                           if not k.startswith("__")})
    split.layers = two_groups
    case = RECORDED["cases"][0]
    logits, kept = qwen2_forward(case, arch=split)
    assert kept.shape[0] == 2
    assert sha(kept) == case["kept_sha256"]
    assert sha(logits) == case["logits_sha256"]


def test_unknown_arch_is_refused(tmp_path):
    root = new_cell_root(tmp_path)
    path = root / "chipbench" / "configs" / "tiny-qwen2.json"
    c = json.loads(path.read_text())
    c["arch"] = "no_such_arch"
    path.write_text(json.dumps(c))
    with pytest.raises(KeyError, match="no_such_arch"):
        cells.load_cell(root, "tiny-qwen2.tiny")


def moe_window_cell(tmp_path):
    root = new_cell_root(tmp_path, "tiny-moe-window",
                         "tiny-moe-window-limits.json", "tiny_moe_window")
    return cells.load_cell(root, "tiny-moe-window.tiny")


@pytest.mark.parametrize("fault", [None, "float8-control", "altered-token"],
                         ids=["sound", "float8-control", "altered-token"])
def test_arch_from_new_files_only(tmp_path, monkeypatch, fault):
    """The test architecture's cell, added as new files only, runs
    correct; the float8 control and a token altered where the decode
    program produces it come out not correct."""
    cell = moe_window_cell(tmp_path)
    assert cell.arch.__name__ == "chipbench_arch_tiny_moe_window"
    cfg = cell.arch.model_config(cell.config)
    assert cfg.moe.top_k == 2 and cfg.attn.global_every == 2
    assert cfg.attn.head_dim * cfg.attn.num_heads != cfg.d_model
    assert not cfg.attn.qkv_bias
    limit = cell.limits["mean_logit_gap"]["limit"]
    seed = 2**31 + 11
    if fault == "float8-control":
        from chipbench import loadgen

        params, lkv = model.make_weights(seed, cfg, arch=cell.arch)
        eng = run.build_engine(cell, cfg, params, lkv, None, 24)
        specs = loadgen.generate(cell.traffic, seed=seed, seconds=3,
                                 vocab_size=cfg.vocab_size)
        done = eng.run(run.requests_of(specs))
        g = run.compare(cell, cfg, params, lkv, done, control=True)
        assert g["mean"] <= limit < g["control_mean"]
        return
    if fault == "altered-token":
        from repro.core import policies

        orig = policies.decode_chunk

        def broken(*a, **k):
            last, cache, toks = orig(*a, **k)
            return last, cache, (toks + 1) % cfg.vocab_size

        monkeypatch.setattr(policies, "decode_chunk", broken)
    res = run.run_cell(cell, seed=seed, seconds=3, trace=False,
                       require_tpu=False)
    assert res["attempted"] == 12 and res["failed"] == 0
    assert res["correct"] is (fault is None), res["checks"]
