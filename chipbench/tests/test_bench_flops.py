"""Analytic operation and byte counts against hand counts, and Qwen2's
counts against those recorded before they moved into its architecture
module."""

import json
import shutil
from pathlib import Path

import pytest

from chipbench import cells, flops

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = ROOT / "chipbench" / "configs"
DATA = Path(__file__).resolve().parent / "data"
QWEN2 = cells.arch_module(ROOT, "qwen2")


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def widths(name):
    return QWEN2.widths(config(name))


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
    assert w.layer_linear_flops() == layer
    # whole model: layer weights, norms, embedding and (untied) head
    per_layer = (layer - w.q_dim - 2 * w.kv_dim) // 2 + w.q_dim \
        + 2 * w.kv_dim + 2 * w.d
    tied = name == "qwen2-1.5b"
    total = w.layers * per_layer + w.vocab * w.d * (1 if tied else 2) + w.d
    assert abs(total / 1e9 - params_b) < 0.01


def test_prefill_and_decode_counts():
    w = widths("qwen2-1.5b")
    lin, L = w.layer_linear_flops(), w.layers
    # one row at position 0 sees one key; unembed once per chunk
    assert w.prefill_chunk_flops(0, 1) == \
        L * (lin + 4 * 12 * 128) + 2 * 1536 * 151936
    # 256 rows from row 1024: keys 1025 .. 1280
    keys = sum(range(1025, 1281))
    assert w.prefill_chunk_flops(1024, 256) == \
        L * (256 * lin + 4 * 12 * 128 * keys) + 2 * 1536 * 151936
    assert w.decode_token_flops(1100) == \
        L * (lin + 4 * 12 * 128 * 1100) + 2 * 1536 * 151936


def test_kernel_calls_and_roofline():
    w = widths("qwen2-7b-tp4")
    (layers, ops, nbytes), = w.chunk_attn_call(512, 256, chips=4)
    keys = sum(range(513, 769))
    assert layers == 28
    assert ops == 4 * 7 * 128 * keys  # 7 query heads per chip
    assert nbytes == 2 * (2 * 256 * 7 * 128 + 2 * 768 * 1 * 128)
    (_, ops, nbytes), = widths("qwen2-1.5b").paged_decode_call(4, 1000)
    assert ops == 4 * 12 * 128 * 1000 * 4
    assert nbytes == 2 * 4 * (2 * 12 * 128 + 2 * 1000 * 2 * 128)
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time_s(1000, 10, peak) == (10.0, "compute")
    assert flops.least_time_s(100, 1000, peak) == (100.0, "bandwidth")
    assert flops.calls_least_s(((3, 1000, 10), (2, 100, 1000)), peak) \
        == 3 * 10.0 + 2 * 100.0


@pytest.mark.parametrize("name", ["qwen2-1.5b", "qwen2-7b-tp4"])
def test_counts_match_the_recorded_ones(name):
    """Every count of a published configuration equals, to the bit, the
    one the harness gave before Qwen2 moved into its module."""
    rec = json.loads((DATA / "qwen2-counts.json").read_text())[name]
    w = widths(name)
    assert w.layers == rec["layers"]
    for start, rows, want in rec["prefill_chunk_flops"]:
        assert w.prefill_chunk_flops(start, rows) == want
    for keys, want in rec["decode_token_flops"]:
        assert w.decode_token_flops(keys) == want
    calls = (("chunk_attn_call", w.chunk_attn_call),
             ("paged_decode_call", w.paged_decode_call),
             ("lookahead_score_call", w.lookahead_score_call))
    for key, call in calls:
        for *args, ops, nbytes in rec[key]:
            assert call(*args) == ((w.layers, ops, nbytes),), (key, args)


def test_head_dim_from_the_file():
    """``head_dim`` is read where the file states it, and is hidden size
    over heads where it does not, in the counts and the program's
    config alike."""
    c = config("qwen2-1.5b")
    assert QWEN2.widths(c).head_dim == 128
    assert QWEN2.model_config(c).attn.head_dim == 128
    c["head_dim"] = 96
    assert QWEN2.widths(c).head_dim == 96
    assert QWEN2.model_config(c).attn.head_dim == 96


@pytest.mark.parametrize("window", [None, 1, 5, 64])
def test_visible_keys_by_row(window):
    for start, rows in ((0, 1), (0, 7), (3, 10), (60, 9), (100, 256)):
        want = sum(p + 1 if window is None else min(p + 1, window)
                   for p in range(start, start + rows))
        assert flops.visible_keys(start, rows, window) == want


def test_windowed_moe_counts(tmp_path):
    """The test architecture's counts: top-k experts per token, and
    windowed keys on its sliding layers."""
    (tmp_path / "chipbench" / "arch").mkdir(parents=True)
    shutil.copy(DATA / "arch" / "tiny_moe_window.py",
                tmp_path / "chipbench" / "arch")
    tiny = cells.arch_module(tmp_path, "tiny_moe_window")
    c = json.loads((DATA / "tiny-moe-window.json").read_text())
    w = tiny.widths(c)
    # 4 layers: 2 see the last 32 keys, 2 are global; 4 q heads of 48,
    # 2 kv heads; router 128 x 8; 2 of 8 experts of width 64
    lin = 2 * (2 * 128 * 192 + 2 * 128 * 96 + 128 * 8 + 2 * 3 * 128 * 64)
    assert w.layer_linear_flops() == lin
    keys_full = sum(range(41, 51))  # rows 40..49
    keys_win = 10 * 32
    assert w.prefill_chunk_flops(40, 10) == \
        2 * (10 * lin + 4 * 4 * 48 * keys_win) \
        + 2 * (10 * lin + 4 * 4 * 48 * keys_full) + 2 * 128 * 512
    assert w.decode_token_flops(100) == \
        2 * (lin + 4 * 4 * 48 * 32) + 2 * (lin + 4 * 4 * 48 * 100) \
        + 2 * 128 * 512
    (n_s, ops_s, b_s), (n_g, ops_g, b_g) = w.chunk_attn_call(40, 10)
    assert (n_s, n_g) == (2, 2)
    assert ops_s == 4 * 4 * 48 * keys_win and ops_g == 4 * 4 * 48 * keys_full
    # the chunk's first row sees keys 9..40, its last 18..49: 41 keys read
    assert b_s == 2 * (2 * 10 * 4 * 48 + 2 * 41 * 2 * 48)
    assert b_g == 2 * (2 * 10 * 4 * 48 + 2 * 50 * 2 * 48)
    (_, ops_s, _), (_, ops_g, _) = w.lookahead_score_call(100, 8)
    assert ops_s == 2 * 8 * 4 * 48 * 32 and ops_g == 2 * 8 * 4 * 48 * 100
