"""Device time in collective ops over busy device time (%)."""

from chipbench import reduce


def read(rec):
    return reduce.collective_pct(rec)
