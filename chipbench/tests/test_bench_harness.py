"""The harness on the CPU at a tiny size: it refuses a machine without a
TPU, finds a cell added as new files only, drives a whole run, and its
correctness check passes the program and fails a broken one and the
float8 control."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import cells, run

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
E2E = [{"name": n, "unit": u, "better": "lower", "bound": 0.25,
        "source": "host_clock"}
       for n, u in (("ttft_p50_ms", "ms"), ("tpot_p95_ms", "ms"),
                    ("setup_s", "s"))]


def new_cell_root(tmp_path: Path, config: str = "tiny-qwen2",
                  limits: str = "tiny-limits.json",
                  arch: str | None = None) -> Path:
    """A checkout whose BENCHMARK.json gains a configuration, a mix and a
    cell ``<config>.tiny`` (and with ``arch`` an architecture module) as
    new files only; the per-layer readers and the architecture modules
    already there are the real ones."""
    cell = f"{config}.tiny"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": config, "source": "test",
                             "file": f"chipbench/configs/{config}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": config,
                               "traffic": "tiny", "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] in ("queue_wait_p50_ms", "idle_share.ttft_p50"):
            m["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    b = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "limits", "arch"):
        (b / sub).mkdir(parents=True)
    shutil.copy(DATA / f"{config}.json", b / "configs" / f"{config}.json")
    shutil.copy(DATA / "tiny-traffic.json", b / "traffic" / "tiny.json")
    shutil.copy(DATA / limits, b / "limits" / f"{cell}.json")
    for f in (ROOT / "chipbench" / "arch").glob("*.py"):
        (b / "arch" / f.name).symlink_to(f)
    if arch is not None:
        shutil.copy(DATA / "arch" / f"{arch}.py", b / "arch" / f"{arch}.py")
    (b / "metrics").symlink_to(ROOT / "chipbench" / "metrics")
    return tmp_path


def tiny_cell(tmp_path):
    return cells.load_cell(new_cell_root(tmp_path), "tiny-qwen2.tiny")


def test_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         "qwen2-1.5b.longctx", "--seed", str(2**31 + 3), "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_cell_from_new_files_only(tmp_path):
    cell = tiny_cell(tmp_path)
    assert cell.config["hidden_size"] == 128 and cell.chips == 1
    assert cell.traffic["name"] == "tiny"
    assert [m["name"] for m in cell.end_to_end] == \
        ["ttft_p50_ms", "tpot_p95_ms", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == \
        ["queue_wait_p50_ms", "idle_share.ttft_p50"]
    for m in cell.per_layer:
        assert callable(cells.metric_reader(cell.root, m["name"]))
    with pytest.raises(KeyError):
        cells.load_cell(cell.root, "no-such-cell")


def test_every_metric_has_a_reader():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert callable(cells.metric_reader(ROOT, m["name"]))
    for w in bench["workloads"]:
        cell = cells.load_cell(ROOT, w["name"])
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
        assert cell.per_layer


def test_tiny_run_is_correct(tmp_path):
    cell = tiny_cell(tmp_path)
    res = run.run_cell(cell, seed=2**31 + 5, seconds=3, trace=False,
                       require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 12 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p50_ms", "tpot_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["compiles_in_window"]["value"] == 0


def test_traced_tiny_run_reports_per_layer(tmp_path):
    cell = tiny_cell(tmp_path)
    res = run.run_cell(cell, seed=4, seconds=3, trace=True,
                       require_tpu=False)
    assert res["correct"], res["checks"]
    # no TPU in the trace: device readers find nothing and are left out
    assert set(res["metrics"]) == {"queue_wait_p50_ms"}
    assert res["metrics"]["queue_wait_p50_ms"]["value"] >= 0
    assert {"busy_s", "window_s"} <= set(res["device"])


def test_altered_token_is_caught(tmp_path, monkeypatch):
    """A token altered where the decode program produces it."""
    from repro.core import policies

    orig = policies.decode_chunk

    def broken(*a, **k):
        last, cache, toks = orig(*a, **k)
        return last, cache, (toks + 1) % 512

    monkeypatch.setattr(policies, "decode_chunk", broken)
    res = run.run_cell(tiny_cell(tmp_path), seed=4, seconds=3, trace=False,
                       require_tpu=False)
    assert not res["correct"]
    c = res["checks"]["mean_logit_gap"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("seed", [3, 4])
def test_float8_control_fails(tmp_path, seed):
    """The control: the reference in float8 puts tokens first whose gap
    in the float32 reference exceeds the limit the program keeps."""
    cell = tiny_cell(tmp_path)
    import jax

    from chipbench import loadgen, model

    cfg = cell.arch.model_config(cell.config)
    params, lkv = model.make_weights(seed, cfg, arch=cell.arch)
    jax.block_until_ready(params)
    eng = run.build_engine(cell, cfg, params, lkv, None, 24)
    specs = loadgen.generate(cell.traffic, seed=seed, seconds=3,
                             vocab_size=cfg.vocab_size)
    done = eng.run(run.requests_of(specs))
    g = run.compare(cell, cfg, params, lkv, done, control=True)
    limit = cell.limits["mean_logit_gap"]["limit"]
    assert g["mean"] <= limit < g["control_mean"]


@pytest.mark.parametrize("exchange", [True, False],
                         ids=["sound", "exchange-left-out"])
def test_four_device_exchange(exchange):
    """The tiny model split over 4 CPU devices: correct when the shards
    exchange their slices, not correct when each keeps its own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    args = [sys.executable, str(Path(__file__).parent / "tiny_tp4.py")]
    p = subprocess.run(args + ([] if exchange else ["--no-exchange"]),
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is exchange, res["checks"]
