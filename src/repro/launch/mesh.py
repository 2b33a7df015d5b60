"""Production meshes (TPU v5e target).

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis extends data parallelism across the slow inter-pod links (DCN-ish);
only gradient/activation all-reduces cross it, never tensor-parallel
collectives.

Functions, not module constants: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before first jax init).

Mesh axes are ``Auto``: shardings are placed by ``param_specs`` /
``device_put`` and the GSPMD partitioner, with the tensor-parallel
kernels under ``shard_map`` (``models/attention.py``).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple, axes: tuple, devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    assert n % model == 0
    return _mesh((n // model, model), ("data", "model"))


def data_axes(mesh) -> tuple:
    """The batch-sharding axes of a mesh: ("pod","data") or ("data",)."""
    names = mesh.axis_names
    return tuple(n for n in names if n != "model")


def axis_size(mesh, name) -> int:
    return mesh.shape[name]
