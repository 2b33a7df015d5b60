"""Rotary position embeddings: standard RoPE, YaRN-scaled RoPE and
qwen2-vl's M-RoPE.

All functions are pure and shape-polymorphic over the batch/seq dims:

    q: (B, S, H, hd)   positions: (B, S) int32   ->  rotated q

M-RoPE (arXiv:2409.12191) splits the head dim into three sections driven by
(temporal, height, width) position streams.  For the language backbone in this
repo the three streams are supplied by ``input_specs`` (text tokens use
t == h == w == absolute index, which makes M-RoPE coincide with RoPE — the
structure is what the dry-run exercises).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import YarnConfig


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    """(head_dim//2,) inverse frequencies, f32."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)


def yarn_ramp(head_dim: int, theta: float, y: YarnConfig) -> tuple[int, int]:
    """(low, high): the pairs between which YaRN's ramp runs.  Pair i
    rotates ``L0 / (2 pi theta^(2i/hd))`` times over the original length
    L0; the ramp starts at the pair that rotates ``beta_fast`` times
    (rounded down) and ends at the one that rotates ``beta_slow`` times
    (rounded up)."""

    def pair(rotations):
        return head_dim * math.log(
            y.original_max_position / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair(y.beta_fast)), 0)
    high = min(math.ceil(pair(y.beta_slow)), head_dim - 1)
    return low, high


def yarn_attention_factor(y: YarnConfig) -> float:
    if y.attention_factor:
        return float(y.attention_factor)
    return 0.1 * math.log(y.factor) + 1.0 if y.factor > 1 else 1.0


def yarn_freqs(head_dim: int, theta: float, y: YarnConfig) -> jnp.ndarray:
    """(head_dim//2,) YaRN inverse frequencies, f32: pairs below the ramp
    keep theirs, pairs above it are divided by ``factor``, and a linear
    ramp blends the two between."""
    base = rope_freqs(head_dim, theta)
    low, high = yarn_ramp(head_dim, theta, y)
    span = high - low if high != low else 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low) / span,
                   0.0, 1.0)
    keep = jnp.asarray(1.0 - ramp, jnp.float32)  # 1: extrapolate (keep)
    return base / y.factor * (1.0 - keep) + base * keep


def _rotate(x: jnp.ndarray, angles: jnp.ndarray,
            scale: float = 1.0) -> jnp.ndarray:
    """x: (..., hd), angles: (..., hd//2) broadcastable; cos and sin are
    multiplied by ``scale`` (YaRN's attention factor) where it is not 1."""
    cos = jnp.cos(angles)
    sin = jnp.sin(angles)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope(
    x: jnp.ndarray,  # (B, S, H, hd)
    positions: jnp.ndarray,  # (B, S) int32
    theta: float,
    yarn: Optional[YarnConfig] = None,
) -> jnp.ndarray:
    hd = x.shape[-1]
    if yarn is None:
        freqs = rope_freqs(hd, theta)  # (hd//2,)
        scale = 1.0
    else:
        freqs = yarn_freqs(hd, theta, yarn)
        scale = yarn_attention_factor(yarn)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (B, S, hd//2)
    return _rotate(x, angles[:, :, None, :], scale)


def apply_mrope(
    x: jnp.ndarray,  # (B, S, H, hd)
    positions: jnp.ndarray,  # (3, B, S) int32: temporal, height, width
    theta: float,
    sections: tuple,  # (t, h, w) half-dim section sizes, sum == hd//2
) -> jnp.ndarray:
    hd = x.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    freqs = rope_freqs(hd, theta)  # (hd//2,)
    # Build per-frequency position source by section.
    sec_ids = jnp.repeat(
        jnp.arange(3), jnp.asarray(sections), total_repeat_length=hd // 2
    )  # (hd//2,) in {0,1,2}
    pos = positions.astype(jnp.float32)  # (3, B, S)
    # (B, S, hd//2): pick the stream per frequency slot.
    pos_per_freq = jnp.take(pos, sec_ids, axis=0)  # (hd//2, B, S)
    pos_per_freq = jnp.moveaxis(pos_per_freq, 0, -1)  # (B, S, hd//2)
    angles = pos_per_freq * freqs
    return _rotate(x, angles[:, :, None, :])


def text_mrope_positions(positions: jnp.ndarray) -> jnp.ndarray:
    """Text-only stream: t == h == w == absolute position.  (B,S)->(3,B,S)."""
    return jnp.broadcast_to(positions[None], (3,) + positions.shape)
