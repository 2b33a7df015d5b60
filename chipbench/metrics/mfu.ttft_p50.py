"""Model operations dispatched in the traced span over the span times the
chips' bf16 peak (%)."""

from chipbench import reduce


def read(rec):
    return reduce.mfu_pct(rec)
