"""``lookahead_score`` kernel: least time at the chip's peaks for the
traced finalize programs' scoring work, over the named kernel's device
time (%)."""

from chipbench import named


def read(rec):
    return named.lookahead_score_roofline_pct(rec)
