"""The traffic generator: deterministic by seed, same work for every seed,
lengths and arrivals inside the mix's ranges."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import loadgen

MIXES = sorted((Path(__file__).resolve().parents[1] / "traffic").glob("*.json"))
BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_deterministic_and_in_range(path):
    mix = json.loads(path.read_text())
    a = loadgen.generate(mix, seed=BIG_SEED, seconds=30, vocab_size=1000)
    b = loadgen.generate(mix, seed=BIG_SEED, seconds=30, vocab_size=1000)
    assert [(s.arrival_s, s.max_new_tokens, s.prompt.tolist()) for s in a] \
        == [(s.arrival_s, s.max_new_tokens, s.prompt.tolist()) for s in b]
    assert len(a) == round(mix["arrival"]["rate_per_s"] * 30)
    p, o = mix["prompt_len"], mix["output_len"]
    for s in a:
        assert p["min"] <= len(s.prompt) <= p["max"]
        assert o["min"] <= s.max_new_tokens <= o["max"]
        assert 0 <= s.arrival_s < 30
        assert s.prompt.dtype == np.int32 and s.prompt.max() < 1000
    arr = [s.arrival_s for s in a]
    assert arr == sorted(arr) and arr[0] == 0.0


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_seeds_share_the_schedule(path):
    """Two seeds: the same lengths and arrivals in the same order, other
    tokens; the lengths and gaps are the mix's stratified draws."""
    mix = json.loads(path.read_text())
    a = loadgen.generate(mix, seed=1, seconds=30, vocab_size=1000)
    b = loadgen.generate(mix, seed=BIG_SEED, seconds=30, vocab_size=1000)
    assert [(len(s.prompt), s.max_new_tokens, s.arrival_s) for s in a] \
        == [(len(s.prompt), s.max_new_tokens, s.arrival_s) for s in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    n = len(a)
    assert sorted(len(s.prompt) for s in a) \
        == loadgen.lengths(mix["prompt_len"], n).tolist()
    gaps = np.diff([s.arrival_s for s in a] + [30.0])
    np.testing.assert_allclose(
        np.sort(gaps), np.sort(loadgen.gaps(mix["arrival"], n)) * 30 / n,
        rtol=1e-9, atol=1e-9)


def test_distributions():
    """Stratified draws follow their distributions: the log-uniform median
    is the geometric mean, the gamma gaps have the asked-for CV."""
    x = loadgen.lengths({"dist": "loguniform", "min": 2048, "max": 16384},
                        1001)
    assert abs(np.median(x) - np.sqrt(2048 * 16384)) < 30
    y = loadgen.lengths({"dist": "lognormal", "median": 512, "sigma": 0.8,
                         "min": 128, "max": 2048}, 1001)
    assert np.median(y) == 512 and y.min() == 128 and y.max() == 2048
    g = loadgen.gaps({"process": "gamma", "cv": 3.0}, 4000)
    assert abs(g.mean() - 1) < 1e-9 and 2.5 < g.std() < 3.2
    e = loadgen.gaps({"process": "poisson"}, 4000)
    assert abs(e.std() - 1) < 0.05


def test_warmup_covers_every_shape():
    mix = {"prompt_len": {"min": 100, "max": 1000}}
    lens = loadgen.warmup_lengths(mix, rung=lambda n: 512 if n <= 480 else 1024,
                                  blocks=lambda n: -(-min(n, 256) // 64))
    keys = {(512 if n <= 480 else 1024, -(-min(n, 256) // 64)) for n in lens}
    want = {(512 if n <= 480 else 1024, -(-min(n, 256) // 64))
            for n in range(100, 1001)}
    assert keys == want and len(lens) == len(want)
