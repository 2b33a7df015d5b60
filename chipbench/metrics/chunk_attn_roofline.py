"""Chunk-attention kernel: least time at the chip's peaks for the calls
the traced prefill chunks needed, over the kernel's device time (%)."""

from chipbench import reduce


def read(rec):
    return reduce.chunk_attn_roofline_pct(rec)
