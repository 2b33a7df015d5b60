"""Block-shape helpers shared by the Pallas TPU kernels.

Mosaic tiles the two minor dimensions of every block into (8·k, 128·k)
vregs: a block's last two dims must be multiples of (8, 128) or span the
whole array.  The kernels therefore take *head-major* operands, whose two
minor dims are (rows, head_dim), and key axes padded to whole
lane-aligned tiles.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def key_tiling(K: int, block_k: int) -> tuple[int, int]:
    """(tile width, padded depth) for a key axis of ``K`` columns: tiles
    are whole multiples of the 128-lane vreg width, and the padded depth
    is a whole number of tiles."""
    bk = min(_round_up(block_k, 128), _round_up(K, 128))
    return bk, _round_up(K, bk)


def head_major(x: jnp.ndarray, rows: int | None = None) -> jnp.ndarray:
    """(B, S, heads, hd) -> (B, heads, S, hd), zero-padding S to ``rows``.
    Its ops run under the ``kv_head_major`` scope, the name a device trace
    finds this re-layout (a full copy of the operand) by."""
    with jax.named_scope("kv_head_major"):
        x = jnp.swapaxes(x, 1, 2)
        if rows is not None and rows > x.shape[2]:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, rows - x.shape[2]), (0, 0)))
    return x
