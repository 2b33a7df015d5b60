"""Analytic operations and bytes, from a configuration's widths.

Counted as the algorithm needs them, not as the program happens to run
them: a multiply-add is 2 operations; attention is counted over the keys a
query may see (its causal prefix in prefill, the kept rows plus the
generated ones in decode); the lookahead rows and padding are not counted.
All counts are for the whole model; ``per_chip`` divides by the chips that
share each layer.
"""

from __future__ import annotations

from dataclasses import dataclass

BF16 = 2  # bytes


@dataclass(frozen=True)
class Widths:
    layers: int
    d: int
    ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int

    @classmethod
    def of(cls, c: dict) -> "Widths":
        h = int(c["num_attention_heads"])
        return cls(layers=int(c["num_hidden_layers"]),
                   d=int(c["hidden_size"]), ff=int(c["intermediate_size"]),
                   heads=h, kv_heads=int(c["num_key_value_heads"]),
                   head_dim=int(c["hidden_size"]) // h,
                   vocab=int(c["vocab_size"]))

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim


def layer_linear_flops(w: Widths) -> int:
    """One token through one layer's projections and MLP (with biases)."""
    proj = w.d * w.q_dim + 2 * w.d * w.kv_dim + w.q_dim * w.d
    mlp = 3 * w.d * w.ff
    return 2 * (proj + mlp) + (w.q_dim + 2 * w.kv_dim)


def attn_flops(w: Widths, keys: float) -> float:
    """One query row of one layer over ``keys`` keys: QK^T and PV."""
    return 4.0 * w.heads * w.head_dim * keys


def unembed_flops(w: Widths) -> int:
    return 2 * w.d * w.vocab


def prefill_chunk_flops(w: Widths, start: int, rows: int) -> float:
    """Prompt rows [start, start + rows) through the model, with the
    logits of the chunk's last row."""
    keys = rows * start + rows * (rows + 1) / 2  # summed causal prefixes
    return (w.layers * (rows * layer_linear_flops(w) + attn_flops(w, 1) * keys)
            + unembed_flops(w))


def decode_token_flops(w: Widths, keys: float) -> float:
    """One generated token attending to ``keys`` cached rows per layer."""
    return (w.layers * (layer_linear_flops(w) + attn_flops(w, keys))
            + unembed_flops(w))


def chunk_attn_call(w: Widths, start: int, rows: int,
                    chips: int = 1) -> tuple[float, float]:
    """(operations, bytes) of one chunk-attention kernel call (one layer,
    one chip): the chunk's queries over keys [0, start + rows) causally;
    it must read q and the visible K/V and write the output."""
    h = w.heads // chips
    kv = w.kv_heads // chips
    keys = rows * start + rows * (rows + 1) / 2
    ops = 4.0 * h * w.head_dim * keys
    nbytes = BF16 * (2 * rows * h * w.head_dim
                     + 2 * (start + rows) * kv * w.head_dim)
    return ops, nbytes


def paged_decode_call(w: Widths, slots: int, keys: float,
                      chips: int = 1) -> tuple[float, float]:
    """(operations, bytes) of one paged flash-decode call (one layer, one
    chip, one step): ``slots`` queries over ``keys`` cached rows each."""
    h = w.heads // chips
    kv = w.kv_heads // chips
    ops = 4.0 * h * w.head_dim * keys * slots
    nbytes = BF16 * slots * (2 * h * w.head_dim + 2 * keys * kv * w.head_dim)
    return ops, nbytes


def least_time_s(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline's least time for the work, and which peak bounds it."""
    t_c = ops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "bandwidth")
