"""Find everything that defines a cell by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
The configuration's entry in ``configs`` gives its file, whose ``arch``
(``qwen2`` where it has none) names its architecture module
``chipbench/arch/<arch>.py``; the mix is
``chipbench/traffic/<traffic>.json``; the correctness limits are
``chipbench/limits/<cell>.json``; each per-layer metric is read by
``chipbench/metrics/<metric>.py``.  A later cell, mix, configuration,
architecture or metric is new files plus new entries, and nothing here
changes.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file
    arch: ModuleType  # its architecture module
    traffic: dict  # the traffic mix file
    limits: dict  # the correctness limits file
    end_to_end: list  # metric entries this cell reports with --trace 0
    per_layer: list  # metric entries this cell reports with --trace 1
    root: Path


def _for_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    """Resolve workload ``name`` from ``<root>/BENCHMARK.json``."""
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / confs[w["config"]]["file"]).read_text())
    arch = arch_module(root, config.get("arch", "qwen2"))
    bench_dir = root / "chipbench"
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((bench_dir / "limits" / f"{name}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _for_cell(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _for_cell(m, name) and m["moves"] in reported]
    return Cell(name=name, chips=int(w["chips"]), config=config, arch=arch,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer, root=root)


def _load(path: Path, prefix: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # where dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root: Path, metric: str):
    """The ``read(rec)`` function of ``chipbench/metrics/<metric>.py``."""
    path = Path(root) / "chipbench" / "metrics" / f"{metric}.py"
    return _load(path, "chipbench_metric_", metric).read


def arch_module(root: Path, arch: str) -> ModuleType:
    """The architecture module ``chipbench/arch/<arch>.py``; a KeyError
    where there is none, so that no configuration falls back to another
    architecture."""
    path = Path(root) / "chipbench" / "arch" / f"{arch}.py"
    if not path.is_file():
        raise KeyError(f"no architecture module {arch!r} ({path})")
    return _load(path, "chipbench_arch_", arch)
