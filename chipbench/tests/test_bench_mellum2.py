"""The mellum2 architecture module at a tiny Mellum2-shaped size
(``data/tiny-mellum2.json``: two periods of sliding x3 then full, window
32, 8 experts held of 16, YaRN on the full layers): its cell comes out
correct; the float8 control, a token altered where the decode program
produces it, and references that forget a mechanism (YaRN off, the window
off, all 16 experts summed where 8 are held) come out not correct."""

import dataclasses
import types

import jax.numpy as jnp
import pytest

from chipbench import cells, loadgen, model, run
from chipbench.tests.test_bench_harness import new_cell_root

SEED = 2**31 + 11


def mellum2_cell(tmp_path):
    root = new_cell_root(tmp_path, "tiny-mellum2", "tiny-mellum2-limits.json")
    return cells.load_cell(root, "tiny-mellum2.tiny")


def mutant(arch, **pieces):
    m = types.ModuleType(arch.__name__ + "_mutant")
    m.__dict__.update({k: v for k, v in vars(arch).items()
                       if not k.startswith("__")})
    m.__dict__.update(pieces)
    return m


def test_tiny_cell_is_mellum2_shaped(tmp_path):
    cell = mellum2_cell(tmp_path)
    assert cell.arch.__name__ == "chipbench_arch_mellum2"
    cfg = cell.arch.model_config(cell.config)
    a, m = cfg.attn, cfg.moe
    assert a.global_every == 4 and a.sliding_window == 32
    assert a.rope_yarn is not None and a.local_rope_yarn is None
    assert (m.num_experts, m.held, m.top_k) == (16, 8, 4)
    assert a.head_dim * a.num_heads != cfg.d_model and not a.qkv_bias


@pytest.mark.parametrize("fault", [None, "altered-token"],
                         ids=["sound", "altered-token"])
def test_tiny_cell_runs(tmp_path, monkeypatch, fault):
    cell = mellum2_cell(tmp_path)
    if fault == "altered-token":
        from repro.core import policies

        orig = policies.decode_chunk
        vocab = int(cell.config["vocab_size"])

        def broken(*a, **k):
            last, cache, toks = orig(*a, **k)
            return last, cache, (toks + 1) % vocab

        monkeypatch.setattr(policies, "decode_chunk", broken)
    res = run.run_cell(cell, seed=SEED, seconds=3, trace=False,
                       require_tpu=False)
    assert res["attempted"] == 12 and res["failed"] == 0
    assert res["correct"] is (fault is None), res["checks"]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One tiny window served by the program, for the reference's
    variants."""
    cell = mellum2_cell(tmp_path_factory.mktemp("mellum2"))
    cfg = cell.arch.model_config(cell.config)
    params, lkv = model.make_weights(SEED, cfg, arch=cell.arch)
    eng = run.build_engine(cell, cfg, params, lkv, None, 24)
    specs = loadgen.generate(cell.traffic, seed=SEED, seconds=3,
                             vocab_size=cfg.vocab_size)
    done = eng.run(run.requests_of(specs))
    return cell, cfg, params, lkv, done


def no_yarn_spec(arch):
    def spec_of(cfg, budget):
        s = arch.spec_of(cfg, budget)
        (theta, _), *rest = s.model
        return s._replace(model=((theta, None), *rest))
    return spec_of


def all_experts(arch):
    """Sums all 16 experts' gated outputs: the 8 held ones and 8 more."""
    def spec_of(cfg, budget):
        s = arch.spec_of(cfg, budget)
        return s._replace(model=(*s.model[:5], 0, 16))
    return spec_of


def sixteen(params):
    layers = dict(params["layers"])
    moe = dict(layers["moe"])
    moe["experts"] = {k: jnp.concatenate([w, w[:, ::-1]], axis=1)
                      for k, w in moe["experts"].items()}
    layers["moe"] = moe
    return dict(params, layers=layers)


@pytest.mark.parametrize("variant", ["float8-control", "yarn-off",
                                     "window-off", "all-16-experts"])
def test_reference_variants_are_caught(served, variant):
    cell, cfg, params, lkv, done = served
    limit = cell.limits["mean_logit_gap"]["limit"]
    if variant == "float8-control":
        g = run.compare(cell, cfg, params, lkv, done, control=True)
        assert g["mean"] <= limit < g["control_mean"], g
        return
    arch = cell.arch
    if variant == "yarn-off":
        mut = mutant(arch, spec_of=no_yarn_spec(arch))
    elif variant == "window-off":
        mut = mutant(arch, window=lambda x, spec: None)
    else:
        mut, params = mutant(arch, spec_of=all_experts(arch)), sixteen(params)
    g = run.compare(dataclasses.replace(cell, arch=mut), cfg, params, lkv,
                    done)
    assert g["mean"] > limit, g
