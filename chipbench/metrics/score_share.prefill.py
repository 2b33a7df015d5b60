"""Share (%) of prefill device time spent in the finalize program
(lookahead rows, ``lookahead_score``, top-k eviction) against the chunk
and finalize programs together."""

from chipbench import reduce


def read(rec):
    return reduce.program_share_pct(rec, "finalize",
                                    ("finalize", "prefill_chunk"))
