"""Run the tiny four-device cell once on the CPU and print its result.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python chipbench/tests/tiny_tp4.py [--no-exchange]

A separate process, because the device count is fixed when JAX starts.
With ``--no-exchange`` every all-gather between the model shards is
replaced by each shard's own slice repeated: the exchange left out.
"""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402

from chipbench import cells, run  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def no_exchange(x, axis_name, *, axis=0, tiled=False, **_):
    n = jax.lax.axis_size(axis_name)
    if tiled:
        return jax.numpy.concatenate([x] * n, axis=axis)
    return jax.numpy.stack([x] * n, axis=axis)


def main() -> None:
    if "--no-exchange" in sys.argv:
        jax.lax.all_gather = no_exchange
    e2e = [{"name": "ttft_p50_ms", "unit": "ms"}, {"name": "setup_s",
                                                   "unit": "s"}]
    cell = cells.Cell(
        name="tiny-qwen2-tp4.tiny", chips=4,
        config=json.loads((DATA / "tiny-qwen2-tp4.json").read_text()),
        arch=cells.arch_module(ROOT, "qwen2"),
        traffic=json.loads((DATA / "tiny-traffic.json").read_text()),
        limits=json.loads((DATA / "tiny-limits.json").read_text()),
        end_to_end=e2e, per_layer=[], root=ROOT)
    res = run.run_cell(cell, seed=4, seconds=2, trace=False,
                       require_tpu=False)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
