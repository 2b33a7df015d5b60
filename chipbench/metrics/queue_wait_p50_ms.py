"""Median wait from a request's due time to the start of its prefill
(the engine's ``request`` span), in ms."""

import numpy as np

from chipbench import reduce


def read(rec):
    w = reduce.queue_waits_ms(rec)
    return float(np.percentile(w, 50)) if w else None
