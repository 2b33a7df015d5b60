"""Share (%) of the traced span in which no op ran on the device."""

from chipbench import reduce


def read(rec):
    return reduce.idle_pct(rec)
