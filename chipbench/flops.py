"""Analytic operations and bytes that every architecture shares.

Counted as the algorithm needs them, not as the program happens to run
them: a multiply-add is 2 operations; attention is counted over the keys a
query may see (its causal prefix in prefill, or the last ``window`` of it
on a windowed layer; the kept rows plus the generated ones in decode); the
lookahead rows and padding are not counted.

An architecture module's ``widths(c)`` (``chipbench/arch/<arch>.py``)
returns the object the metric readers take every count from, built on the
functions here:

- ``layers``;
- ``prefill_chunk_flops(start, rows)`` and ``decode_token_flops(keys)``:
  the whole model's operations;
- ``chunk_attn_call(start, rows, chips)``,
  ``paged_decode_call(slots, keys, chips)`` and
  ``lookahead_score_call(n_prompt, n_obs, chips)``: the kernels' calls,
  one per layer on each chip, as ``((layers, ops, bytes), ...)`` with one
  entry for each kind of layer (``calls_least_s`` sums their least time).
"""

from __future__ import annotations

BF16 = 2  # bytes
F32 = 4


def visible_keys(start: int, rows: int, window: int | None = None) -> float:
    """Keys summed over query rows ``[start, start + rows)``, each seeing
    its causal prefix, or only the last ``window`` keys of it."""
    if window is None:
        return rows * start + rows * (rows + 1) / 2
    # row at position p sees min(p + 1, window) keys
    full = max(0, min(start + rows, window) - start)
    return full * start + full * (full + 1) / 2 + (rows - full) * window


def attn_flops(heads: int, head_dim: int, keys: float) -> float:
    """One query row of one layer over ``keys`` keys: QK^T and PV."""
    return 4.0 * heads * head_dim * keys


def chunk_attn_call(heads: int, kv_heads: int, head_dim: int, start: int,
                    rows: int, chips: int = 1,
                    window: int | None = None) -> tuple[float, float]:
    """(operations, bytes) of one chunk-attention kernel call (one layer,
    one chip): the chunk's queries over the keys each may see; it must
    read q and the visible K/V and write the output."""
    h = heads // chips
    kv = kv_heads // chips
    keys = visible_keys(start, rows, window)
    first = 0 if window is None else max(0, start - window + 1)
    ops = 4.0 * h * head_dim * keys
    nbytes = BF16 * (2 * rows * h * head_dim
                     + 2 * (start + rows - first) * kv * head_dim)
    return ops, nbytes


def paged_decode_call(heads: int, kv_heads: int, head_dim: int, slots: int,
                      keys: float, chips: int = 1) -> tuple[float, float]:
    """(operations, bytes) of one paged flash-decode call (one layer, one
    chip, one step): ``slots`` queries over ``keys`` cached rows each."""
    h = heads // chips
    kv = kv_heads // chips
    ops = 4.0 * h * head_dim * keys * slots
    nbytes = BF16 * slots * (2 * h * head_dim + 2 * keys * kv * head_dim)
    return ops, nbytes


def lookahead_score_call(heads: int, kv_heads: int, head_dim: int,
                         n_prompt: int, n_obs: int,
                         chips: int = 1) -> tuple[float, float]:
    """(operations, bytes) of one ``lookahead_score`` call (one layer, one
    chip): QK^T of the ``n_obs`` lookahead rows of every head over the
    ``n_prompt`` prompt keys; it must read q and K once and write the f32
    scores.  The kernel's second pass over K (the masses, against the
    final softmax statistics) is not counted: the algorithm needs one."""
    h = heads // chips
    kv = kv_heads // chips
    ops = 2.0 * n_obs * h * head_dim * n_prompt
    nbytes = (BF16 * (n_obs * h + n_prompt * kv) * head_dim
              + F32 * h * n_prompt)
    return ops, nbytes


def least_time_s(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline's least time for the work, and which peak bounds it."""
    t_c = ops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "bandwidth")


def calls_least_s(calls, peak: dict) -> float:
    """Least time of kernel calls given as ``((layers, ops, bytes), ...)``."""
    return sum(n * least_time_s(ops, nbytes, peak)[0]
               for n, ops, nbytes in calls)
