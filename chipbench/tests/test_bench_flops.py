"""Analytic operation and byte counts against hand counts."""

import json
from pathlib import Path

import pytest

from chipbench import flops

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def widths(name):
    return flops.Widths.of(json.loads((CONFIGS / f"{name}.json").read_text()))


@pytest.mark.parametrize("name,layer,params_b", [
    # per layer: q, o (d x d), k, v (d x 256 or d x 512), gate/up/down;
    # 2 operations per weight plus the three bias adds
    ("qwen2-1.5b", 2 * (2 * 1536 * 1536 + 2 * 1536 * 256
                        + 3 * 1536 * 8960) + 1536 + 512, 1.54),
    ("qwen2-7b-tp4", 2 * (2 * 3584 * 3584 + 2 * 3584 * 512
                          + 3 * 3584 * 18944) + 3584 + 1024, 7.62),
])
def test_layer_flops(name, layer, params_b):
    w = widths(name)
    assert flops.layer_linear_flops(w) == layer
    # whole model: layer weights, norms, embedding and (untied) head
    per_layer = (layer - w.q_dim - 2 * w.kv_dim) // 2 + w.q_dim \
        + 2 * w.kv_dim + 2 * w.d
    tied = name == "qwen2-1.5b"
    total = w.layers * per_layer + w.vocab * w.d * (1 if tied else 2) + w.d
    assert abs(total / 1e9 - params_b) < 0.01


def test_prefill_and_decode_counts():
    w = widths("qwen2-1.5b")
    lin, L = flops.layer_linear_flops(w), w.layers
    # one row at position 0 sees one key; unembed once per chunk
    assert flops.prefill_chunk_flops(w, 0, 1) == \
        L * (lin + 4 * 12 * 128) + 2 * 1536 * 151936
    # 256 rows from row 1024: keys 1025 .. 1280
    keys = sum(range(1025, 1281))
    assert flops.prefill_chunk_flops(w, 1024, 256) == \
        L * (256 * lin + 4 * 12 * 128 * keys) + 2 * 1536 * 151936
    assert flops.decode_token_flops(w, 1100) == \
        L * (lin + 4 * 12 * 128 * 1100) + 2 * 1536 * 151936


def test_kernel_calls_and_roofline():
    w = widths("qwen2-7b-tp4")
    ops, nbytes = flops.chunk_attn_call(w, 512, 256, chips=4)
    keys = sum(range(513, 769))
    assert ops == 4 * 7 * 128 * keys  # 7 query heads per chip
    assert nbytes == 2 * (2 * 256 * 7 * 128 + 2 * 768 * 1 * 128)
    ops, nbytes = flops.paged_decode_call(widths("qwen2-1.5b"), 4, 1000)
    assert ops == 4 * 12 * 128 * 1000 * 4
    assert nbytes == 2 * 4 * (2 * 12 * 128 + 2 * 1000 * 2 * 128)
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time_s(1000, 10, peak) == (10.0, "compute")
    assert flops.least_time_s(100, 1000, peak) == (100.0, "bandwidth")
